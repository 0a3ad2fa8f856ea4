"""`ops/ssm_step.py`: the Mamba-1 decode step's state update. The kernel in
interpret mode against the `jax.numpy` form, with slots inactive, none live,
all live, a traced layer index inside a scan, and the null row and every
other layer's rows left as they were. `ops/ssm_scan.py`: the prefill's scan
as a kernel against the chunked scan it stands in for, alone, under `vmap`
and through `prefill`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops.ssm_step import live_slots, ssm_step, ssm_step_reference

L, R, N, DI = 3, 6, 16, 256


def _inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    S = jax.random.normal(ks[0], (L, 1 + R, N, DI), jnp.float32).at[:, 0].set(0.0)
    dt = jax.random.uniform(ks[1], (R, DI), jnp.float32, 1e-3, 0.2)
    u = jax.random.normal(ks[2], (R, DI), jnp.float32)
    B = jax.random.normal(ks[3], (R, N), jnp.float32)
    C = jax.random.normal(ks[4], (R, N), jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[5], (N, DI), jnp.float32))
    D = jax.random.normal(ks[6], (DI,), jnp.float32)
    return S, dt, u, B, C, A, D


ACTIVE = {
    "all": np.ones(R, bool), "none": np.zeros(R, bool),
    "some": np.array([True, False, True, True, False, False]),
    "last": np.array([False] * (R - 1) + [True]), "unmasked": None,
}


@pytest.mark.parametrize("which", sorted(ACTIVE))
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_step_against_the_recurrence_with_slots_inactive(impl, which):
    S, dt, u, B, C, A, D = _inputs()
    active = None if ACTIVE[which] is None else jnp.asarray(ACTIVE[which])
    mask = np.ones(R, bool) if ACTIVE[which] is None else ACTIVE[which]
    y, S1 = ssm_step(S, dt, u, B, C, A, D, 1, active, impl=impl, interpret=True)
    y_ref, rows = ssm_step_reference(S[1, 1:], dt, u, B, C, A, D)
    np.testing.assert_allclose(np.asarray(S1[1, 1:])[mask], np.asarray(rows)[mask],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[mask], np.asarray(y_ref)[mask], rtol=1e-5, atol=1e-5)
    # a slot not active keeps its state to the bit and reads 0; the null row
    # and the other layers' rows are untouched
    np.testing.assert_array_equal(np.asarray(S1[1, 1:])[~mask], np.asarray(S[1, 1:])[~mask])
    assert (np.asarray(y)[~mask] == 0).all()
    np.testing.assert_array_equal(np.asarray(S1[1, 0]), 0)
    np.testing.assert_array_equal(np.asarray(S1)[[0, 2]], np.asarray(S)[[0, 2]])


def test_the_two_forms_agree_to_float32_rounding():
    S, dt, u, B, C, A, D = _inputs(3)
    active = jnp.asarray(ACTIVE["some"])
    got = ssm_step(S, dt, u, B, C, A, D, 2, active, impl="pallas", interpret=True)
    want = ssm_step(S, dt, u, B, C, A, D, 2, active, impl="xla")
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_traced_layer_index_inside_a_scan(impl):
    """The layer's place in the pool as a scan's counter (a scanned run of
    layers): each layer's rows updated once, from that layer's rows."""
    S, dt, u, B, C, A, D = _inputs(5)
    active = jnp.asarray(ACTIVE["some"])
    live = live_slots(active, R)

    @jax.jit
    def run(S):
        def body(S, i):
            y, S = ssm_step(S, dt, u, B, C, A, D, i, active, impl=impl, live=live,
                            interpret=True)
            return S, y

        return jax.lax.scan(body, S, jnp.arange(L, dtype=jnp.int32))

    S1, ys = run(S)
    for i in range(L):
        y_ref, rows = ssm_step_reference(S[i, 1:], dt, u, B, C, A, D)
        m = ACTIVE["some"]
        np.testing.assert_allclose(np.asarray(S1[i, 1:])[m], np.asarray(rows)[m], rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(ys[i])[m], np.asarray(y_ref)[m], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(S1[i, 1:])[~m], np.asarray(S[i, 1:])[~m])


def test_the_work_list_names_the_live_slots_first():
    order, n = live_slots(jnp.asarray(ACTIVE["some"]), R)
    assert int(n) == 3 and order.tolist() == [0, 2, 3, 1, 4, 5]
    order, n = live_slots(None, R)
    assert int(n) == R and order.tolist() == list(range(R))


def test_a_bf16_state_is_another_result():
    """Thirty-two steps with the state rounded to bf16 after each: the state
    moves by parts in a thousand where float32 arithmetic in another order
    moves it by parts in ten million (the benchmark's `STATE_STEP_REL_TOL`
    5e-5 lies between, with room on both sides)."""
    S, dt, u, B, C, A, D = _inputs(7)
    dt = dt * 0.1  # slow decays: a step's rounding is still there many steps on
    rows32, rows16, rows_p = S[0, 1:], S[0, 1:], S
    for _ in range(32):
        _, rows32 = ssm_step_reference(rows32, dt, u, B, C, A, D)
        _, rows16 = ssm_step_reference(rows16, dt, u, B, C, A, D)
        rows16 = rows16.astype(jnp.bfloat16).astype(jnp.float32)
        _, rows_p = ssm_step(rows_p, dt, u, B, C, A, D, 0, None, impl="pallas", interpret=True)
    scale = float(jnp.max(jnp.abs(rows32)))
    assert float(jnp.max(jnp.abs(rows_p[0, 1:] - rows32))) / scale < 5e-6
    assert float(jnp.max(jnp.abs(rows16 - rows32))) / scale > 5e-4


# -- ops/ssm_scan.py: the prefill's scan ------------------------------------------------


def _scan_inputs(T, Di=256, seed=0, real=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(ks[0], (T, Di))
    dt = jax.random.uniform(ks[1], (T, Di), jnp.float32, 1e-3, 0.3)
    if real is not None:
        dt = dt.at[real:].set(0.0)  # padding: decay 1, input 0
    B, C = jax.random.normal(ks[2], (T, N)), jax.random.normal(ks[3], (T, N))
    return u, dt, B, C, -jnp.exp(jax.random.normal(ks[4], (N, Di)))


@pytest.mark.parametrize("T,real", [(64, None), (96, 80), (45, 45), (200, 131)])
def test_scan_kernel_against_the_chunked_scan(T, real):
    """Lengths that are and are not whole blocks of tokens, padding past the
    true length leaving the state as the last real token left it."""
    from areal_tpu.models.qwen2 import PADDING_SEGMENT, _ssm_chunk_scan
    from areal_tpu.ops.ssm_scan import ssm_scan

    u, dt, B, C, A = _scan_inputs(T, real=real)
    seg = jnp.where(jnp.arange(T) < (real or T), 0, PADDING_SEGMENT)

    def scan(*a):
        return _ssm_chunk_scan(*a, seg)

    y0, h0 = scan(u, dt, B, C, A)
    y1, h1 = ssm_scan(u, dt, B, C, A, scan=scan, impl="pallas", interpret=True)
    n = real or T
    np.testing.assert_allclose(np.asarray(y1[:n]), np.asarray(y0[:n]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-5, rtol=1e-5)
    # the XLA form IS the caller's scan
    y2, h2 = ssm_scan(u, dt, B, C, A, scan=scan, impl="xla")
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y0))


def test_scan_kernel_under_vmap_and_through_prefill(monkeypatch):
    """A wave of sequences (`jax.vmap`: one more grid axis), and the model's
    `prefill` with the kernel in the scan's place: the same rows and state."""
    from areal_tpu.models.qwen2 import _ssm_chunk_scan
    from areal_tpu.ops import ssm_scan as op

    u, dt, B, C, A = _scan_inputs(64, seed=3)
    seg = jnp.zeros(64, jnp.int32)
    y0, h0 = _ssm_chunk_scan(u, dt, B, C, A, seg)
    yb, hb = jax.vmap(lambda u, dt: op.ssm_scan(u, dt, B, C, A, scan=None, impl="pallas",
                                                interpret=True))(
        jnp.stack([u, 2 * u]), jnp.stack([dt, dt]))
    np.testing.assert_allclose(np.asarray(yb[0]), np.asarray(y0), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hb[1]), 2 * np.asarray(h0), atol=2e-5, rtol=1e-5)

    from test_jamba import CFG, _ids, seeded
    from areal_tpu.models.qwen2 import prefill

    params = seeded(CFG)
    ids = jnp.asarray(np.r_[_ids(5, 40), np.zeros(24, np.int32)])
    want = prefill(params, ids, jnp.arange(64), CFG, valid=jnp.arange(64) < 40)
    monkeypatch.setattr(op, "resolve_impl", lambda impl: "pallas")
    monkeypatch.setattr(op, "_default_interpret", lambda: True)
    got = prefill(params, ids, jnp.arange(64), CFG, valid=jnp.arange(64) < 40)
    text = jax.jit(lambda p: prefill(p, ids, jnp.arange(64), CFG)[0]).lower(params).as_text(
        debug_info=True)
    assert "attn/ssm_scan" in text
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape[:1] == (64,):  # logits: the real rows
            a, b = a[:40], b[:40]
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
