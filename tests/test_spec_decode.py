"""Draft-free speculative decoding (spec_decode="ngram"): correctness.

The verify chunk scores up to spec_k draft positions in one forward and
accepts the longest prefix matching what greedy/sampling would have
emitted — so every emitted token is, by construction, the token the
non-speculative oracle produces, and these tests pin the strong form of
that claim: tokens identical to `spec_decode="off"` and logprobs equal to
float32 rounding (the verify chunk is another compiled program) across
forks, suffix prefills, stop boundaries mid-accepted-draft and rejection
rewinds under run-ahead (both attention impls with speculation on:
tests/test_paged_attention.py::test_engine_impl_parity). Plus the
telemetry, prewarm coverage, and the honest per-token ITL accounting.
"""

import asyncio
import time

import numpy as np
import pytest

from _numerics import assert_logprobs_close

import jax

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import (
    JaxDecodeEngine,
    _Inflight,
    _Slot,
    _ngram_draft,
)
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params

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

_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_params(TINY, jax.random.PRNGKey(0))
    return _PARAMS


def _echo_params():
    """Zero the residual-mixing kernels: greedy decoding becomes a
    deterministic last-token map, which must enter a short cycle — a
    synthetic stand-in for the prompt-quoting repetition of trained
    math/code rollouts, with GUARANTEED n-gram acceptance once the cycle
    repeats."""
    p = init_params(TINY, jax.random.PRNGKey(0))
    layers = dict(p["layers"])
    layers["attn"] = {
        **layers["attn"], "o_kernel": layers["attn"]["o_kernel"] * 0.0
    }
    layers["mlp"] = {
        **layers["mlp"], "down_kernel": layers["mlp"]["down_kernel"] * 0.0
    }
    return {**p, "layers": layers}


def _make_engine(spec: str, params=None, tokenizer=None, **kw):
    cfg = JaxDecodeConfig(
        context_length=kw.pop("context_length", 256),
        max_running_requests=kw.pop("max_running_requests", 4),
        new_tokens_per_chunk=kw.pop("new_tokens_per_chunk", 4),
        decode_runahead_chunks=kw.pop("decode_runahead_chunks", 1),
        spec_decode=spec,
        spec_k=kw.pop("spec_k", 4),
        spec_ngram_max=kw.pop("spec_ngram_max", 3),
        dtype="float32",
        kv_cache_dtype="float32",
        random_seed=kw.pop("random_seed", 5),
        **kw,
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=tokenizer)
    eng.set_model(params if params is not None else _params(), TINY)
    eng.initialize()
    return eng


def _run_requests(eng, reqs):
    async def run_all():
        return await asyncio.gather(*[eng.agenerate(r) for r in reqs])

    return asyncio.run(run_all())


def _gather_spec_pair(make_reqs, **kw):
    """Run the same request set on a spec-off and a spec-on engine;
    returns (off, on, on_metrics)."""
    outs = []
    metrics = None
    for spec in ("off", "ngram"):
        eng = _make_engine(spec, **kw)
        try:
            outs.append(_run_requests(eng, make_reqs()))
            if spec == "ngram":
                metrics = eng.get_metrics()
        finally:
            eng.destroy()
    return outs[0], outs[1], metrics


# ---------------------------------------------------------------------------
# drafter unit behavior
# ---------------------------------------------------------------------------


def test_ngram_draft_prompt_lookup():
    # trailing 3-gram [2, 3, 4] matched at its earlier occurrence, the
    # continuation (overlapping into the suffix — self-extension) proposed
    assert _ngram_draft([1, 2, 3, 4, 9, 2, 3, 4], 3, 3) == [9, 2, 3]
    # most RECENT occurrence wins
    assert _ngram_draft([5, 1, 7, 5, 2, 7, 5], 2, 2) == [2, 7]
    # longest n wins over a shorter, more recent match
    assert _ngram_draft([1, 2, 3, 9, 9, 1, 2, 3], 2, 3)[0] == 9
    # no earlier occurrence -> no draft; degenerate inputs -> no draft
    assert _ngram_draft([1, 2, 3, 4], 4, 3) == []
    assert _ngram_draft([7], 4, 3) == []
    assert _ngram_draft([1, 1, 1], 0, 3) == []
    # periodic context: the draft IS the next period
    assert _ngram_draft([4, 5, 6] * 4, 5, 3) == [4, 5, 6, 4, 5]


# ---------------------------------------------------------------------------
# bit-identity vs the non-speculative oracle
# ---------------------------------------------------------------------------


def test_greedy_bit_identical_spec(cpu_devices):
    """Greedy streams and logprobs bitwise-equal to spec_decode="off",
    across same-wave duplicate forks and a >=64-token suffix prefill."""

    def make_reqs():
        g = GenerationHyperparameters(greedy=True, max_new_tokens=10)
        base = [1, 5, 9, 13, 2, 4, 6, 8]
        reqs = [
            ModelRequest(input_ids=list(base), gconfig=g),
            ModelRequest(input_ids=list(base), gconfig=g),  # dup -> fork
            # periodic prompt: the drafter proposes from the first chunk on
            ModelRequest(input_ids=[3, 7, 11] * 5, gconfig=g),
            ModelRequest(input_ids=[2, 7, 11, 3], gconfig=g),
        ]
        return reqs

    off, on, m = _gather_spec_pair(make_reqs)
    for i, (a, b) in enumerate(zip(off, on)):
        assert a.output_tokens == b.output_tokens, i
        assert_logprobs_close(a.output_logprobs, b.output_logprobs, i)
        assert a.stop_reason == b.stop_reason, i
    # the spec engine really dispatched verify chunks and drafted tokens
    assert m["spec_chunks_total"] > 0
    assert m["spec_drafted_tokens_total"] > 0
    assert m["prefix_forks_total"] >= 1


def test_greedy_bit_identical_spec_suffix_prefill(cpu_devices):
    """A conversation extension past the 64-token shared-prefix floor
    (fork + suffix prefill) stays bit-identical with speculation on."""

    def run(spec):
        eng = _make_engine(spec)
        try:
            g = GenerationHyperparameters(greedy=True, max_new_tokens=8)
            long_prompt = [(i % 60) + 1 for i in range(70)]
            donor = eng.generate(
                ModelRequest(input_ids=list(long_prompt), gconfig=g),
                timeout=300,
            )
            ext = eng.generate(
                ModelRequest(
                    input_ids=list(long_prompt)
                    + list(donor.output_tokens)
                    + [5, 3],
                    gconfig=g,
                ),
                timeout=300,
            )
            m = eng.get_metrics()
            return [donor, ext], m
        finally:
            eng.destroy()

    off, _ = run("off")
    on, m = run("ngram")
    for i, (a, b) in enumerate(zip(off, on)):
        assert a.output_tokens == b.output_tokens, i
        assert_logprobs_close(a.output_logprobs, b.output_logprobs, i)
    assert m["suffix_prefills_total"] >= 1, m


def test_sampled_bit_identical_spec(cpu_devices):
    """Sampled streams with MIXED top-p classes in one batch: the verify
    chunk flattens positions through the same sampler with the same
    fold_in(base_key, position) keys, so speculation cannot perturb any
    slot's stream — including co-scheduled top_p == 1 slots that must
    keep the primary subkey."""

    def make_reqs():
        reqs = []
        for i in range(5):
            prompt = ([1 + i, 9, 4] * 3) if i % 2 else [1 + i, 9, 4]
            reqs.append(
                ModelRequest(
                    input_ids=prompt,
                    gconfig=GenerationHyperparameters(
                        temperature=1.0,
                        top_p=0.9 if i % 2 else 1.0,
                        max_new_tokens=9,
                    ),
                )
            )
        return reqs

    off, on, m = _gather_spec_pair(make_reqs)
    for i, (a, b) in enumerate(zip(off, on)):
        assert a.output_tokens == b.output_tokens, i
        assert_logprobs_close(a.output_logprobs, b.output_logprobs, i)
    assert m["spec_chunks_total"] > 0


# ---------------------------------------------------------------------------
# stop handling + rejection rewind
# ---------------------------------------------------------------------------


class DigitTok:
    eos_token_id = None

    def decode(self, ids):
        return "".join(str(i % 10) for i in ids)


def test_stop_string_lands_mid_accepted_draft(cpu_devices):
    """A stop string completing INSIDE an accepted draft run must truncate
    exactly where the oracle truncates: the verify chunk emitted past the
    boundary in one batch, and _truncate_at_stop + the retire rewind drop
    the overrun."""
    prompt = [2, 7, 11, 3]
    g_probe = GenerationHyperparameters(greedy=True, max_new_tokens=24)

    eng_off = _make_engine("off", params=_echo_params(), tokenizer=DigitTok())
    try:
        full = eng_off.generate(
            ModelRequest(input_ids=prompt, gconfig=g_probe), timeout=300
        ).output_tokens
        text = "".join(str(t % 10) for t in full)
        # deepest stop string with a determinate FIRST completion: inside
        # the established cycle every short window repeats each period, so
        # scan (boundary, length) pairs for the latest boundary a window
        # (anchored into the unique pre-cycle prefix) first completes at —
        # deep enough that drafts are already riding accepted
        boundary, stop_s = 0, ""
        for b in range(6, len(full) + 1):
            for L in range(2, min(14, b) + 1):
                cand = text[b - L : b]
                if cand not in text[: b - 1]:
                    if b > boundary:
                        boundary, stop_s = b, cand
                    break
        assert boundary >= 8, (boundary, text)
        assert stop_s not in text[: boundary - 1]
        g_stop = GenerationHyperparameters(
            greedy=True, max_new_tokens=24, stop=[stop_s]
        )
        oracle = eng_off.generate(
            ModelRequest(input_ids=prompt, gconfig=g_stop), timeout=300
        )
    finally:
        eng_off.destroy()
    assert oracle.stop_reason == "stop"
    assert oracle.output_tokens == full[:boundary]

    eng = _make_engine(
        "ngram", params=_echo_params(), tokenizer=DigitTok(), spec_k=7
    )
    try:
        resp = eng.generate(
            ModelRequest(input_ids=prompt, gconfig=g_stop), timeout=300
        )
        m = eng.get_metrics()
        assert resp.stop_reason == "stop"
        assert resp.output_tokens == oracle.output_tokens
        assert resp.output_logprobs == oracle.output_logprobs
        # the stop really landed in speculative territory: drafts were
        # accepted during this run (echo params guarantee the cycle)
        assert m["spec_accepted_per_chunk_mean"] > 0, m
        # quiesce: the retire rewound the slot to the TRUE end (prompt[:-1]
        # + consumed tokens), not the verify chunk's worst-case horizon
        eng.pause_generation()
        assert not eng._inflight
        keys = [k for k in eng._slot_prefix if k is not None]
        assert keys and len(keys[0]) == len(prompt) - 1 + len(
            resp.output_tokens
        )
    finally:
        eng.destroy()


def test_rejection_rewind_under_runahead(cpu_devices):
    """Rejected drafts + a stop token found mid-chunk while the NEXT
    verify chunk is already in flight (runahead=1): the speculative
    tokens are discarded, the worst-case length projection reconciles,
    and the donor registration covers exactly the true end."""
    prompt = [1, 5, 9, 13, 2]

    def greedy_ref(params, p, n):
        seq = list(p)
        for _ in range(n):
            T = len(seq)
            logits = forward(
                params,
                np.array(seq, dtype=np.int32),
                np.arange(T, dtype=np.int32),
                np.zeros(T, dtype=np.int32),
                TINY,
            )
            seq.append(int(np.argmax(np.asarray(logits[-1]))))
        return seq[len(p):]

    eng = _make_engine("ngram", decode_runahead_chunks=1)
    try:
        full = greedy_ref(eng.params, prompt, 12)
        stop_tok = full[5]
        cut = full.index(stop_tok) + 1
        resp = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(
                    greedy=True, max_new_tokens=12, stop_token_ids=[stop_tok]
                ),
            ),
            timeout=300,
        )
        assert resp.stop_reason == "stop"
        assert resp.output_tokens == full[:cut]
        eng.pause_generation()
        assert not eng._inflight
        # every worst-case projection must have reconciled away: retired
        # slot lengths are zeroed, the donor registration is the true end
        assert all(int(x) == 0 for x in eng._slot_lengths)
        keys = [k for k in eng._slot_prefix if k is not None]
        assert keys and len(keys[0]) == len(prompt) - 1 + cut
        m = eng.get_metrics()
        assert m["generated_tokens_total"] == cut
        eng.continue_generation()
        # engine stays healthy after the rewind
        resp2 = eng.generate(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=6),
            ),
            timeout=300,
        )
        assert resp2.output_tokens == full[:6]
    finally:
        eng.destroy()


# ---------------------------------------------------------------------------
# the verify op
# ---------------------------------------------------------------------------


def test_paged_verify_op_pallas_matches_xla(cpu_devices):
    """Op level: the q_len>1 Pallas split-KV verify kernel (interpret mode
    on CPU) agrees with the gather+verify_attention XLA path."""
    from areal_tpu.ops.paged_attention import paged_attention_qlen

    rng = np.random.RandomState(3)
    R, W, nH, nKV, hd, bsz, nb = 3, 4, 4, 2, 16, 8, 3
    n_blocks = 1 + R * nb
    q = rng.randn(R, W, nH, hd).astype(np.float32)
    # the stored layout: [L, n_blocks, bsz, nKV*hd]; the op reads layer 1
    kp = rng.randn(2, n_blocks, bsz, nKV * hd).astype(np.float32)
    vp = rng.randn(2, n_blocks, bsz, nKV * hd).astype(np.float32)
    bt = np.arange(1, 1 + R * nb, dtype=np.int32).reshape(R, nb)
    base = np.array([5, 11, 0], dtype=np.int32)
    pos = base[:, None] + np.arange(W)[None, :]
    valid = np.arange(nb * bsz)[None, None, :] <= pos[:, :, None]
    import jax.numpy as jnp

    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(valid), 1)
    out_x = paged_attention_qlen(*args, impl="xla")
    out_p = paged_attention_qlen(*args, impl="pallas", interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_p), atol=1e-5
    )


# ---------------------------------------------------------------------------
# telemetry, prewarm, ITL accounting
# ---------------------------------------------------------------------------


def test_spec_metrics_accounting(cpu_devices):
    """On the echo workload the acceptance telemetry must show real
    acceptance and stay internally consistent: histogram mass equals the
    verify-chunk count, drafted = accepted + rejected, and the mean
    accepted-per-chunk clears 1.0 (the bench acceptance bar)."""
    eng = _make_engine(
        "ngram", params=_echo_params(), spec_k=7, new_tokens_per_chunk=8
    )
    try:
        g = GenerationHyperparameters(greedy=True, max_new_tokens=96)
        eng.generate(
            ModelRequest(input_ids=[2, 7, 11, 3], gconfig=g), timeout=300
        )
        m = eng.get_metrics()
        assert m["spec_decode"] == "ngram"
        assert m["spec_chunks_total"] > 0
        hist = m["spec_accepted_per_chunk"]
        assert sum(hist.values()) == m["spec_chunks_total"]
        accepted = sum(int(k) * v for k, v in hist.items())
        assert (
            m["spec_drafted_tokens_total"]
            == accepted + m["spec_rejected_tokens_total"]
        )
        assert m["spec_accepted_per_chunk_mean"] > 1.0, m
        assert 0.0 < m["spec_draft_hit_rate"] <= 1.0
        assert (
            m["spec_emitted_per_chunk_mean"]
            == pytest.approx(m["spec_accepted_per_chunk_mean"] + 1.0)
        )
    finally:
        eng.destroy()


def test_prewarm_compiles_verify_variants(cpu_devices):
    """Prewarm must ghost-compile every (q-width bucket x sampler class x
    nb bucket) verify variant the drafter can select, alongside the
    normal chunk variants — no first-request compile stall when
    spec_decode='ngram' is live."""
    eng = _make_engine(
        "ngram", context_length=1024, max_running_requests=2, spec_k=4
    )
    try:
        eng.prewarm(prompt_len=200, new_tokens=80, include_fork=False)
        bsz = eng._alloc.block_size
        assert eng._spec_draft_buckets() == [1, 2, 4]
        spec_k = int(eng.config.spec_k)
        for b in eng._expected_chunk_buckets(200, 80, grow=spec_k + 1):
            nb = -(-b // bsz)
            for use_topp in (False, True):
                # normal chunk variants still covered
                for db in eng._spec_draft_buckets():
                    assert (use_topp, nb, db + 1) in eng._verify_fns, (
                        use_topp, nb, db + 1, list(eng._verify_fns),
                    )
        for b in eng._expected_chunk_buckets(200, 80):
            nb = -(-b // bsz)
            for use_topp in (False, True):
                assert (use_topp, False, nb) in eng._chunk_fns
    finally:
        eng.destroy()


def test_consume_divides_by_emitted_tokens(cpu_devices):
    """Regression (ISSUE 6 satellite): per-token ITL divides the device
    window by tokens actually emitted (accepted + bonus), NOT the
    dispatched draft width — a verify chunk that emitted 3 of 5
    dispatched positions delivered 3 tokens in that window."""
    eng = _make_engine("ngram", spec_k=4)
    try:
        eng.pause_generation()
        R = eng.config.max_running_requests
        item = _Slot(
            rid="itl-test",
            prompt=[1, 2, 3],
            gconfig=GenerationHyperparameters(max_new_tokens=100),
            future=None,
            loop=None,
        )
        eng._slots[0] = item
        eng._slot_lengths[0] = 2 + 5  # base 2, worst-case projected +W
        W = 5
        active = np.zeros(R, dtype=bool)
        active[0] = True
        rec = _Inflight(
            toks=np.full((W, R), 7, dtype=np.int32),
            logps=np.zeros((W, R), dtype=np.float32),
            items=list(eng._slots),
            active=active,
            epochs=eng._slot_epoch.copy(),
            version=0,
            t_dispatch=time.monotonic() - 0.9,
            n_chunk=W,
            spec_w=W,
            accepted=np.array([2] + [0] * (R - 1), dtype=np.int32),
            draft_lens=np.array([4] + [0] * (R - 1), dtype=np.int32),
        )
        eng._consume_chunk(rec)
        # accepted 2 + bonus = 3 emitted tokens
        assert len(item.tokens) == 3
        assert len(item.itl) == 3
        # each per-token ITL ~= 0.9s / 3 = 0.3s; dividing by the dispatched
        # width W=5 would report ~0.18s — the dishonest number
        for v in item.itl:
            assert 0.25 < v < 0.45, item.itl
        # worst-case projection reconciled: 7 - (W - emitted) = 5
        assert int(eng._slot_lengths[0]) == 5
        m = eng.get_metrics()
        assert m["spec_chunks_total"] == 1
        assert m["spec_rejected_tokens_total"] == 2  # drafted 4, accepted 2
        eng._slots[0] = None
        eng.continue_generation()
    finally:
        eng.destroy()
