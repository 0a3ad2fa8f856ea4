"""The decode engine works on the KV pool in place.

The pool is stored `[L, n_blocks, block_size, nKV*hd]`, the layout the paged
kernel reads, and every program that touches it takes it whole and gives it
back whole: the only operations allowed on it are the scatter that writes
rows, the gather of a slot's blocks, and the kernel. This test traces the
engine's own programs (the paged chunk, the speculative verify chunk, a
batched prefill; Pallas impl, interpret mode) and holds them to that: no
reshape, slice, stack, copy or cast has an operand or a result as large as one
layer's slice of the pool, no scan takes the pool as `xs` or gives it as `ys`,
and the lowered programs alias every donated pool argument to a result. On the
v5e those operations were three quarters of a decode chunk (PERF.md, PRs 24
and 25); a change that brings one back fails here, on the CPU. The default
engine here (the XLA attention read in place of the kernel) is the same
program with that one read swapped: it gathers a slot's blocks inside
`layer/attn/attention` and holds no workspace anywhere else.
"""

import re
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import InferenceEngineConfig, JaxDecodeConfig
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import ModelConfig, init_params

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)
R, CONTEXT, PAGE, CHUNK = 4, 256, 16, 4

# what moved the pool on the chip, and their kin: shape changes, slices,
# stacking, copies and casts. Scatter, gather and the kernel are not here.
MOVERS = {
    "reshape", "transpose", "squeeze", "expand_dims", "dynamic_slice", "slice",
    "dynamic_update_slice", "concatenate", "copy", "copy_p",
    "convert_element_type", "broadcast_in_dim", "select_n", "pad",
}
DTYPES = {"bfloat16": "bf16", "int8": "i8", "float32": "f32"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):  # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":  # its body sees one page
            for sub in _sub_jaxprs(eqn):
                yield from _walk(sub)


def _pool_movers(jaxpr, slice_elems):
    found = []
    for eqn in _walk(jaxpr):
        name = eqn.primitive.name
        if name in MOVERS:
            moved = (*eqn.invars, *eqn.outvars)
        elif name == "scan":
            # what a scan takes as xs it slices per step, and what it gives
            # as ys it stacks: the pool may only be a constant or a carry
            held = eqn.params["num_consts"] + eqn.params["num_carry"]
            moved = (*eqn.invars[held:], *eqn.outvars[eqn.params["num_carry"]:])
            name = "scan xs/ys"
        else:
            continue
        big = [
            v.aval.str_short() for v in moved
            if hasattr(v.aval, "shape") and int(np.prod(v.aval.shape)) >= slice_elems
        ]
        if big:
            found.append(f"{name} {big}")
    return found


@pytest.fixture(
    scope="module",
    params=[("fp", True), ("fp", False), ("int8", True), ("int8", False)],
    ids=["bf16-scan", "bf16-unrolled", "int8-scan", "int8-unrolled"],
)
def engine(request, cpu_devices):
    kv_dtype, scan = request.param
    cfg = replace(TINY, scan_layers=scan)
    eng = JaxDecodeEngine(
        JaxDecodeConfig(
            context_length=CONTEXT, max_running_requests=R,
            new_tokens_per_chunk=CHUNK, page_size=PAGE, dtype="float32",
            kv_cache_dtype="bfloat16", kv_dtype=kv_dtype,
            paged_attn_impl="pallas", spec_decode="ngram", spec_k=2,
        ),
        InferenceEngineConfig(),
    )
    eng.set_model(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    eng.initialize()
    eng.pause_generation()
    try:
        yield eng
    finally:
        eng.destroy()


def _programs(eng, nb=2, W=3):
    """(name, jitted program, arguments) as the scheduler dispatches them."""
    bucket, B = 32, 2
    kq, vq = eng._kv_operands()
    step_args = (
        eng.params, kq, vq, jnp.zeros((R, nb), jnp.int32),
        jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32), jnp.ones(R, bool),
        jnp.zeros((R, 2), jnp.uint32), jnp.ones(R, jnp.float32),
        jnp.ones(R, jnp.float32), jnp.zeros(R, bool), jnp.zeros(R, jnp.int32),
    )
    return [
        ("chunk", eng._get_chunk_fn(False, False, nb), step_args),
        ("verify_chunk", eng._get_verify_fn(False, nb, W),
         (*step_args, jnp.zeros((R, W - 1), jnp.int32), jnp.zeros(R, jnp.int32))),
        ("prefill_batched", eng._get_batched_prefill_fn(bucket, B),
         (eng.params, kq, vq, jnp.zeros((B, bucket), jnp.int32),
          jnp.arange(bucket, dtype=jnp.int32),
          jnp.ones((B, bucket // PAGE), jnp.int32), jnp.full(B, 20, jnp.int32))),
    ]


def test_pool_is_stored_as_the_kernel_reads_it(engine):
    L, nKV, hd = TINY.num_hidden_layers, TINY.num_key_value_heads, TINY.head_dim_
    n_blocks = R * (CONTEXT // PAGE) + 1
    assert engine._k_cache.shape == engine._v_cache.shape == (L, n_blocks, PAGE, nKV * hd)
    if engine._k_scale is not None:
        assert engine._k_cache.dtype == jnp.int8
        assert engine._k_scale.shape == (L, n_blocks, nKV, PAGE)


@pytest.mark.parametrize("program", ["chunk", "verify_chunk", "prefill_batched"])
def test_program_never_moves_the_pool(engine, program):
    name, fn, args = next(p for p in _programs(engine) if p[0] == program)
    slice_elems = int(np.prod(engine._k_cache.shape[1:]))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    prims = {e.primitive.name for e in _walk(jaxpr)}
    # the walk reached the model: the row scatter, and in the two decode
    # programs the kernel
    assert "scatter" in prims, sorted(prims)
    assert program == "prefill_batched" or "pallas_call" in prims, sorted(prims)
    movers = _pool_movers(jaxpr, slice_elems)
    assert not movers, f"{name} moves the pool: {movers}"


@pytest.mark.parametrize("nb", [2, 4, 16])
def test_chunk_scores_a_group_of_pages_from_two_buffers_a_pool(engine, nb):
    """The decode chunk's kernel takes the group its shapes give (eight pages
    of these 16-row pages, clipped to the table's columns) into two group
    buffers a pool, whatever the bucket; the verify chunk's a page. Nothing
    else of the program changes with the group: the pool is still read where
    it is."""
    from areal_tpu.ops.paged_attention import pool_group_pages

    def buffers(fn, args):
        calls = [e for e in _walk(jax.make_jaxpr(fn)(*args).jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert calls and not _pool_movers(jax.make_jaxpr(fn)(*args).jaxpr, int(
            np.prod(engine._k_cache.shape[1:])))
        D = engine._k_cache.shape[-1]
        return {tuple(v.aval.shape) for e in calls for v in e.params["jaxpr"].invars
                if len(v.aval.shape) == 3 and v.aval.shape[0] == 2 and v.aval.shape[2] == D
                and v.aval.dtype == engine._k_cache.dtype}

    progs = {name: (fn, args) for name, fn, args in _programs(engine, nb=nb)}
    pages = pool_group_pages(engine._kv_operands()[0], 1, nb)
    assert pages == min(8, nb)
    D = engine._k_cache.shape[-1]
    assert buffers(*progs["chunk"]) == {(2, pages * PAGE, D)}
    assert buffers(*progs["verify_chunk"]) == {(2, PAGE, D)}


@pytest.mark.parametrize("program", ["chunk", "verify_chunk", "prefill_batched"])
def test_program_aliases_the_pool_to_its_results(engine, program):
    """Donated and carried through, the pool goes in and comes out in the
    same buffers: every pool leaf is aliased to the result in its place."""
    name, fn, args = next(p for p in _programs(engine) if p[0] == program)
    text = fn.lower(*args).as_text()
    sig = text[text.index("func.func public @main("):]
    sig = sig[: sig.index(") -> (")]
    # one piece per argument: its type, then its attributes
    aliased = {}
    for piece in re.split(r"%arg\d+: ", sig)[1:]:
        m = re.search(r"tf\.aliasing_output = (\d+)", piece)
        if m:
            aliased[int(m.group(1))] = re.match(r"tensor<([^>]*)>", piece).group(1)
    want = {
        j: "x".join(map(str, a.shape)) + "x" + DTYPES[a.dtype.name]
        for j, a in enumerate(jax.tree.leaves(args[1:3]))
    }
    assert aliased == want, f"{name}: {aliased} != {want}"


# -- the default engine on the CPU: the same program, one read swapped ----------


@pytest.fixture(scope="module")
def default_engine(cpu_devices):
    """`paged_attn_impl` left at "auto": the XLA read off a TPU."""
    eng = JaxDecodeEngine(
        JaxDecodeConfig(
            context_length=CONTEXT, max_running_requests=R,
            new_tokens_per_chunk=CHUNK, page_size=PAGE, dtype="float32",
            kv_cache_dtype="bfloat16", spec_decode="ngram", spec_k=2,
        ),
        InferenceEngineConfig(),
    )
    eng.set_model(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.initialize()
    eng.pause_generation()
    try:
        yield eng
    finally:
        eng.destroy()


@pytest.mark.parametrize("program", ["chunk", "verify_chunk"])
def test_default_engine_carries_the_pool_and_gathers_only_to_attend(
    default_engine, program
):
    """The token loop and the layer loop carry the pool whole (never `xs` or
    `ys`, never moved), and a value as large as one layer of a gathered
    workspace `[R, nb*bsz, nKV, hd]` exists only under
    `layer/attn/attention`, where the XLA read gathers the slot's blocks
    (`pool_read`); one as large as `[L, R, nb*bsz, nKV, hd]` nowhere."""
    assert default_engine._paged_impl == "xla"
    nb, W = 4, 3
    name, fn, args = next(
        p for p in _programs(default_engine, nb=nb, W=W) if p[0] == program
    )
    pool = default_engine._k_cache
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    movers = _pool_movers(jaxpr, int(np.prod(pool.shape[1:])))
    assert not movers, f"{name} moves the pool: {movers}"
    scans = [e for e in _walk(jaxpr) if e.primitive.name == "scan"]
    carrying = [
        e for e in scans
        if sum(v.aval.shape == pool.shape for v in e.outvars[: e.params["num_carry"]]) == 2
    ]
    # the token loop (the chunk only) and the layer loop
    assert len(carrying) == len(scans) == (2 if program == "chunk" else 1)
    layer_ws = R * nb * PAGE * pool.shape[-1]
    gathered, outside = 0, []
    for eqn in _walk(jaxpr):
        scope = str(eqn.source_info.name_stack)
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if shape == pool.shape or int(np.prod(shape)) < layer_ws:
                continue
            assert int(np.prod(shape)) < TINY.num_hidden_layers * layer_ws, (scope, shape)
            if "layer/attn/attention" in scope:
                gathered += scope.endswith("attention/pool_read")
            else:
                outside.append(f"{eqn.primitive.name} {shape} under {scope!r}")
    assert gathered, "the walk never reached the XLA read's gather"
    assert not outside, f"{name} holds a workspace outside attention: {outside}"
