"""What a checkpointed layer's backward keeps (`utils/hbm.py:REMAT_SETS`,
`models/qwen2.py:_maybe_remat`, `engine/jax_engine.py:_remat_kept`): kept and
recomputed values come from the same operations on the same operands, so every
set gives full recompute's loss and gradients to the bit, for every kind of
layer and attention path; the trainer chooses a set once a shape, says so in
`train_batch`'s stats, and the step it takes is full recompute's."""

import dataclasses
import logging as pylogging
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import MicroBatchSpec, OptimizerConfig, TrainEngineConfig
from areal_tpu.engine import jax_engine
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.utils import hbm
from tests import test_deepseek_v2, test_kimi_linear, test_qwen3next

DENSE = ModelConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, dtype="float32", param_dtype="float32")
SPARSE = dataclasses.replace(
    DENSE, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    router_aux_loss_coef=0.01)
FC = dataclasses.replace(
    DENSE, mlp_style="fc", norm_type="layernorm", pos_embed="learned",
    hidden_act="gelu_new", attn_out_bias=True, max_position_embeddings=512)

# kind of model: (config, attention path, tokens, equal to the bit). Where XLA's
# CPU code for a recurrence (the linear mixers' chunk scans) or a LayerNorm is
# fused differently around a kept value, the last bits move as they do between
# full recompute and no recompute at all: those kinds are held to that.
MODELS = {
    "dense-flash": (DENSE, "flash", 256, True),
    "dense-xla": (DENSE, "dense", 96, True),
    "dense-chunked": (DENSE, "chunked", 96, True),
    "dense-ring": (DENSE, "ring", 512, True),
    "dense-unstacked": (dataclasses.replace(DENSE, scan_layers=False), "dense", 96, True),
    "sparse-xla": (SPARSE, "dense", 96, True),
    "sparse-ring": (SPARSE, "ring", 512, True),
    "latent": (test_deepseek_v2.FULL, "dense", 96, True),
    "fc-xla": (FC, "dense", 96, False),
    "linear-gdn": (test_qwen3next.FULL, "dense", 96, False),
    "linear-kda-latent": (test_kimi_linear.FULL, "dense", 96, False),
}


@pytest.fixture()
def ring_mesh(cpu_devices):
    mesh = mesh_lib.build_mesh(ParallelStrategy(data_parallel_size=4), devices=cpu_devices[:4])
    mesh_lib.set_current_mesh(mesh)
    yield mesh
    mesh_lib.set_current_mesh(None)


def _loss_and_grads(cfg, T, kept):
    params = init_params(cfg, jax.random.PRNGKey(3))
    ids = (jnp.arange(T) * 7 + 3) % cfg.vocab_size
    seg = jnp.where(jnp.arange(T) < T - 8, jnp.arange(T) // (T // 3 + 1), -1)
    first = jnp.searchsorted(seg[: T - 8], seg[: T - 8])
    pos = jnp.concatenate([jnp.arange(T - 8) - first, jnp.zeros(8, jnp.int32)])

    def loss(p):
        logits, aux = forward(p, ids, pos, seg, cfg, with_aux=True, remat_kept=kept)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.roll(ids, -1)[:, None], axis=-1)[:, 0]
        return -(picked * (seg >= 0)).sum() + 0.01 * aux

    return jax.jit(jax.value_and_grad(loss))(params)


def _worst(grads, ref) -> float:
    """Largest |difference| of two gradient trees, a leaf's against the
    larger of its own scale and a hundredth of the tree's (a leaf whose true
    gradient is zero, a key bias under LayerNorm, holds rounding alone)."""
    top = max(float(np.abs(np.asarray(b)).max()) for b in jax.tree.leaves(ref))
    return max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        / max(float(np.abs(np.asarray(b)).max()), 0.01 * top)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref)))


@pytest.mark.parametrize("model", list(MODELS))
def test_every_set_gives_full_recomputes_loss_and_gradients(model, request):
    cfg, impl, T, exact = MODELS[model]
    if impl == "ring":
        request.getfixturevalue("ring_mesh")
    cfg = dataclasses.replace(cfg, remat=True, attn_impl=impl)
    full_loss, full_grads = _loss_and_grads(cfg, T, hbm.REMAT_SETS[0])
    assert np.isfinite(float(full_loss))
    if not exact:
        # the yardstick: full recompute against no checkpoint region at all
        _, plain = _loss_and_grads(dataclasses.replace(cfg, remat=False), T, ())
        slack = max(4 * _worst(plain, full_grads), 1e-6)
        assert slack < 1e-3, slack
    for kept in hbm.REMAT_SETS[1:]:
        loss, grads = _loss_and_grads(cfg, T, kept)
        assert float(loss) == float(full_loss), (model, len(kept))
        if not exact:
            assert _worst(grads, full_grads) <= slack, (model, len(kept), slack)
            continue
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(full_grads)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (model, len(kept), path)


@pytest.mark.parametrize("model", ["dense-flash", "dense-xla", "sparse-xla", "latent", "linear-gdn"])
def test_a_set_takes_its_matmuls_out_of_the_backward(model):
    """The lowered grad program holds fewer matrix products with each set kept
    (the projections' second run, then the MLP's): the names reach the
    policy. A layer without a name for something keeps recomputing it."""
    cfg, impl, T, _ = MODELS[model]
    cfg = dataclasses.replace(cfg, remat=True, attn_impl=impl)
    params = init_params(cfg, jax.random.PRNGKey(3))
    ids = jnp.arange(T) % cfg.vocab_size
    seg = jnp.zeros(T, jnp.int32)

    def dots(kept):
        def loss(p):
            return forward(p, ids, jnp.arange(T), seg, cfg, remat_kept=kept).sum()
        return jax.jit(jax.grad(loss)).lower(params).as_text().count("dot_general")

    counts = [dots(kept) for kept in hbm.REMAT_SETS]
    plain = not (cfg.latent or any(cfg.layer_linear(i) for i in range(cfg.num_hidden_layers)))
    dense_mlp = not all(cfg.layer_sparse(i) for i in range(cfg.num_hidden_layers))
    assert counts[1] < counts[0] if plain else counts[1] <= counts[0], counts
    assert counts[2] < counts[1] if dense_mlp else counts[2] == counts[1], counts


def test_remat_off_names_nothing_and_keeps_everything():
    """`gradient_checkpointing: false` (`cfg.remat` False): no checkpoint
    region, whatever names are passed."""
    cfg = dataclasses.replace(DENSE, attn_impl="dense")
    a = _loss_and_grads(cfg, 96, ())
    b = _loss_and_grads(cfg, 96, hbm.REMAT_SETS[2])
    assert float(a[0]) == float(b[0])
    params = init_params(cfg, jax.random.PRNGKey(3))
    text = jax.jit(jax.grad(lambda p: forward(
        p, jnp.arange(96) % 64, jnp.arange(96), jnp.zeros(96, jnp.int32), cfg,
        remat_kept=hbm.REMAT_SETS[2]).sum())).lower(params).as_text()
    assert "checkpoint" not in text and "remat" not in text


# -- the trainer ---------------------------------------------------------------


def _engine(monkeypatch, capacity):
    from areal_tpu.engine.sft.lm_engine import JaxLMEngine

    def capacity_of(kind):
        if capacity is None:
            raise ValueError(f"no HBM capacity known for device kind {kind!r}")
        return capacity

    monkeypatch.setattr(hbm, "hbm_bytes", capacity_of)
    # the account of what engines hold starts empty: other tests' engines,
    # collected or not, are not on this test's chip
    monkeypatch.setattr(hbm, "_DECLARED", weakref.WeakKeyDictionary())
    eng = JaxLMEngine(TrainEngineConfig(
        experiment_name="t", trial_name="t", path="", init_from_scratch=True, dtype="float32",
        mb_spec=MicroBatchSpec(max_tokens_per_mb=256),
        optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0,
                                  lr_scheduler_type="constant"),
        gradient_checkpointing=True))
    eng.model_config = dataclasses.replace(DENSE, attn_impl="dense", remat=True)
    eng.create_process_group(ParallelStrategy(data_parallel_size=8))
    eng.initialize(None, None)
    return eng


def _sft_steps(eng, steps=2):
    B, T = 6, 40
    ids = (np.arange(B * T, dtype=np.int64).reshape(B, T) * 5 + 1) % 64
    batch = dict(input_ids=ids, attention_mask=np.ones((B, T), np.int64),
                 loss_mask=np.pad(np.ones((B, T - 4), np.int64), ((0, 0), (4, 0))))
    out = [eng.train_lm({k: np.copy(v) for k, v in batch.items()}) for _ in range(steps)]
    return out, jax.tree.map(np.asarray, eng.params)


def _room_for(n_sets, eng_tokens):
    """Room for exactly `n_sets` of the sets at the tiny engine's
    micro-batches (8 chips over dp): between two sets' bytes."""
    need = [hbm.remat_kept_bytes(DENSE, eng_tokens // 8, n) for n in range(3)]
    room = need[n_sets] + (64 if n_sets == 2 else (need[n_sets + 1] - need[n_sets]) // 2)
    return room


def _room_capacity(monkeypatch, sets, tokens=256):
    """A capacity at which the tiny engine keeps exactly `sets` at `tokens`:
    (resident + the full-recompute step + room) / margin."""
    probe = _engine(monkeypatch, 1 << 40)
    try:
        resident = probe._own_bytes()
        est = hbm.estimate_train_hbm(probe.model_config, dp=8, microbatch_tokens=tokens)
        step = est.activation_bytes + est.logits_bytes + est.grad_transient_bytes
    finally:
        probe.destroy()
    return int((resident + step + _room_for(sets, tokens)) / hbm.REMAT_ROOM_MARGIN) + 1


@pytest.mark.parametrize("sets", [0, 1, 2])
def test_trainer_says_what_it_keeps_and_takes_full_recomputes_step(
        cpu_devices, monkeypatch, sets):
    """`remat_kept_sets` / `remat_kept_bytes` in `train_batch`'s stats, one
    choice a micro-batch shape, no compile beyond full recompute's, and
    losses, grad norms and updated parameters equal to full recompute's."""
    ref = _engine(monkeypatch, None)  # a chip of unknown capacity keeps nothing
    try:
        ref_stats, ref_params = _sft_steps(ref)
        assert ref._remat_choice and set(ref._remat_choice.values()) == {(0, 0)}
    finally:
        ref.destroy()

    tokens = 256
    eng = _engine(monkeypatch, _room_capacity(monkeypatch, sets, tokens))
    said: list[str] = []
    listener = pylogging.Handler()
    listener.emit = lambda record: said.append(record.getMessage())
    jax_engine.logger.addHandler(listener)
    try:
        stats, params = _sft_steps(eng)
        choices = dict(eng._remat_choice)
    finally:
        jax_engine.logger.removeHandler(listener)
        eng.destroy()
    assert list(choices) == [(tokens, True)], choices
    n, kept = choices[tokens, True]
    assert n == sets and kept == hbm.remat_kept_bytes(DENSE, tokens // 8, sets)
    for s, r in zip(stats, ref_stats):
        assert s["remat_kept_sets"] == sets and s["remat_kept_bytes"] == float(kept)
        assert r["remat_kept_sets"] == 0 and r["remat_kept_bytes"] == 0.0
        assert s["compiles"] == r["compiles"]
        assert s["loss"] == r["loss"] and s["grad_norm"] == r["grad_norm"]
    assert stats[1]["compiles"] == 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(ref_params)):
        assert np.array_equal(a, b), path
    said = [line for line in said if "grad_step T=" in line]
    want = ("nothing", "attention", "attention + mlp")[sets]
    assert len(said) == 1 and f"T={tokens // 8}/chip: keeping {want}," in said[0], said


def test_a_shape_traced_again_gets_the_choice_it_had(cpu_devices, monkeypatch):
    """The choice is remembered a shape and a head (the fused head changes
    the step's own bytes): whatever the chip's room comes to later, the
    program traced again for that shape is the program the warm-up made."""
    eng = _engine(monkeypatch, 1 << 40)
    try:
        first = eng._remat_kept(256)
        assert first[0] == 2
        monkeypatch.setattr(hbm, "hbm_bytes", lambda kind: 1)  # no room at all
        assert eng._remat_kept(256) == first
        assert eng._remat_kept(384) == (0, 0)
        assert eng._remat_kept(256, fused_head=False) == (0, 0)
        assert set(eng._remat_choice) == {(256, True), (384, True), (256, False)}
    finally:
        eng.destroy()


class _Chip:
    """A device of a mesh as `_remat_kept` sees one: of this process or of
    another, whose `memory_stats` cannot be asked (jaxlib: "MemoryStats is
    only supported for addressable PjRt devices")."""

    device_kind = "TPU v5 lite"

    def __init__(self, process_index, in_use=None):
        self.process_index, self.in_use, self.asked = process_index, in_use, 0

    def memory_stats(self):
        self.asked += 1
        if self.in_use is None:
            raise RuntimeError("MemoryStats is only supported for addressable PjRt devices")
        return {"bytes_in_use": self.in_use}


@pytest.mark.parametrize("here", [0, 1], ids=["owns_device_0", "owns_another"])
def test_the_choice_asks_no_device_of_another_process(cpu_devices, monkeypatch, here):
    """On a multi-host mesh every process makes the same choice, from shapes
    and declared bytes alone: a process that does not own the mesh's first
    device never asks it for `memory_stats`, and what its own chip has in
    use (a reading that differs between hosts) is checked, said in the log
    when the account misses it, and moves nothing."""
    import types

    capacity = _room_capacity(monkeypatch, 1)
    choices = []
    for in_use in (0, 1 << 50):
        eng = _engine(monkeypatch, capacity)
        chips = [_Chip(0, in_use if here == 0 else None),
                 _Chip(1, in_use if here == 1 else None)]
        said: list[str] = []
        listener = pylogging.Handler()
        listener.emit = lambda record: said.append(record.getMessage())
        jax_engine.logger.addHandler(listener)
        real_mesh, own = eng.mesh, eng._own_bytes()
        try:
            eng._own_bytes = lambda: own  # (its shardings want the real mesh)
            eng.mesh = types.SimpleNamespace(
                shape=real_mesh.shape, devices=np.array(chips, dtype=object))
            with monkeypatch.context() as m:
                m.setattr(jax, "process_index", lambda: here)
                choices.append(eng._remat_kept(256))
        finally:
            jax_engine.logger.removeHandler(listener)
            eng.mesh = real_mesh
            eng.destroy()
        assert chips[1 - here].asked == 0 and chips[here].asked == 1
        assert any("not in the account" in line for line in said) == bool(in_use)
    assert choices[0] == choices[1] and choices[0][0] == 1


def test_another_engines_declared_bytes_leave_less_room(cpu_devices, monkeypatch):
    """What a co-resident engine declared (`hbm.declare_resident`: a decode
    engine's weights and pools, a critic's state) comes off the room; an
    engine that is destroyed or collected is off the account."""
    capacity = _room_capacity(monkeypatch, 2)
    eng = _engine(monkeypatch, capacity)
    try:
        assert hbm.declared_resident_bytes() == eng._own_bytes() > 0
        assert hbm.declared_resident_bytes(but=eng) == 0

        class Other:
            pass

        other = Other()
        mlp = hbm.remat_kept_bytes(DENSE, 256 // 8, 2) - hbm.remat_kept_bytes(DENSE, 256 // 8, 1)
        hbm.declare_resident(other, mlp)
        assert hbm.declared_resident_bytes(but=eng) == mlp
        assert eng._remat_kept(256)[0] == 1
        hbm.declare_resident(other, 1 << 40)
        assert eng._remat_kept(128) == (0, 0)
        del other
        import gc

        gc.collect()
        assert hbm.declared_resident_bytes(but=eng) == 0
        assert eng._remat_kept(64)[0] == 2
    finally:
        eng.destroy()
    assert hbm.declared_resident_bytes() == 0


def test_a_refused_program_steps_down_a_set(cpu_devices, monkeypatch):
    """A grad step that has never run and that the chip refuses for memory
    is made again keeping one set fewer, the shape's choice lowered for good
    and said in the log; the step it then takes is full recompute's. Any
    other error, and a refusal with nothing kept, is raised as it came."""
    ref = _engine(monkeypatch, None)
    try:
        ref_stats, ref_params = _sft_steps(ref)
    finally:
        ref.destroy()
    eng = _engine(monkeypatch, 1 << 40)
    refused: list[tuple] = []
    real_run = eng._run

    def run(program, fn, shape_key, *args, **static):
        if program == "grad_step" and len(static["kept"]) > len(hbm.REMAT_SETS[1]):
            refused.append(static["kept"])
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
                "of memory in memory space hbm. Used 17.50G of 15.75G hbm.")
        return real_run(program, fn, shape_key, *args, **static)

    eng._run = run
    said: list[str] = []
    listener = pylogging.Handler()
    listener.emit = lambda record: said.append(record.getMessage())
    jax_engine.logger.addHandler(listener)
    try:
        stats, params = _sft_steps(eng)
        assert refused == [hbm.REMAT_SETS[2]]
        assert eng._remat_choice[256, True] == (1, hbm.remat_kept_bytes(DENSE, 256 // 8, 1))
        # an error that is not the chip's refusal for memory is not answered
        eng._run = lambda *a, **k: (_ for _ in ()).throw(
            jax.errors.JaxRuntimeError("INTERNAL: something else"))
        with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
            _sft_steps(eng, steps=1)
        assert eng._remat_choice[256, True][0] == 1
    finally:
        jax_engine.logger.removeHandler(listener)
        eng.destroy()
    for s, r in zip(stats, ref_stats):
        assert s["remat_kept_sets"] == 1
        assert s["compiles"] == r["compiles"]
        assert s["loss"] == r["loss"] and s["grad_norm"] == r["grad_norm"]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(ref_params)):
        assert np.array_equal(a, b), path
    assert sum("the chip refused the program that keeps 2 of 2 sets" in line
               and line.endswith("keeping 1") for line in said) == 1, said
