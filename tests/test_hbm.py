"""HBM estimator: exact param counts, plan fit/reject decisions, and the
AOT compile-check that proves a full-depth 7B program builds on a CPU host.

The estimator (utils/hbm.py) is the feasibility half of VERDICT r4 #4: an
allocation plan is validated against the chip's HBM *before* launch, and
`plan_compile_check` AOT-compiles the real sharded train step (full depth
28, full width, full vocab) without materializing a single parameter."""

import jax
import pytest

from areal_tpu.api.alloc_mode import (
    AllocationMode,
    AllocationValidationError,
    ParallelStrategy,
)
from areal_tpu.models.qwen2 import ModelConfig, init_params
from areal_tpu.utils import hbm

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

QWEN25_05B = ModelConfig(
    vocab_size=151936,
    hidden_size=896,
    intermediate_size=4864,
    num_hidden_layers=24,
    num_attention_heads=14,
    num_key_value_heads=2,
    tie_word_embeddings=True,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

QWEN25_7B = ModelConfig(
    vocab_size=152064,
    hidden_size=3584,
    intermediate_size=18944,
    num_hidden_layers=28,
    num_attention_heads=28,
    num_key_value_heads=4,
    tie_word_embeddings=False,
    dtype="bfloat16",
    param_dtype="bfloat16",
)


def _actual_count(cfg):
    p = init_params(cfg, jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(p))


def test_param_count_exact_dense_and_tied():
    assert hbm.param_count(TINY) == _actual_count(TINY)
    # the known flagship number: Qwen2.5-0.5B = 494M
    assert hbm.param_count(QWEN25_05B) == _actual_count(QWEN25_05B) == 494032768


def test_param_count_exact_moe():
    moe = ModelConfig(
        vocab_size=64,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=48,
        dtype="float32",
        param_dtype="float32",
    )
    assert hbm.param_count(moe) == _actual_count(moe)


def test_05b_bench_config_fits_v5e():
    """The config the r03/r05 bench actually ran on one v5e chip (bf16
    packed SFT, 8192-token micro-batches) must be judged feasible."""
    est = hbm.estimate_train_hbm(QWEN25_05B, microbatch_tokens=8192)
    hbm.check_fit(est, "TPU v5 lite")  # must not raise
    # adamw f32 moments dominate: 2 x 494M x 4B ~ 3.7 GiB
    assert 3.2 * hbm.GiB < est.opt_bytes < 4.2 * hbm.GiB
    assert est.total_bytes < 16 * hbm.GiB


def test_7b_rejected_on_one_v5e_accepted_on_v5p_mesh():
    single = hbm.estimate_train_hbm(QWEN25_7B, microbatch_tokens=8192)
    with pytest.raises(MemoryError, match="GiB"):
        hbm.check_fit(single, "TPU v5 lite")
    # the documented v5p plan: fsdp dp=8 x tp=4 (docs/PARITY.md "7B recipe")
    sharded = hbm.estimate_train_hbm(
        QWEN25_7B, dp=8, tp=4, microbatch_tokens=8192
    )
    hbm.check_fit(sharded, "TPU v5p")  # must not raise
    # opt state per chip: 2 x 7.6B x 4 / 32 ~ 1.9 GiB
    assert sharded.opt_bytes < 2.5 * hbm.GiB


def test_alloc_mode_check_hbm_integration():
    mode = AllocationMode.from_str("jax:d4t4+d8t4")
    report = mode.check_hbm(QWEN25_7B, "TPU v5p", microbatch_tokens=8192)
    assert "train" in report and "gen" in report
    assert report["train"]["total_gib"] < 95 * 0.9
    # on v5e the gen half's dense 64x32k KV reservation is what breaks
    with pytest.raises(AllocationValidationError, match="gen half"):
        mode.check_hbm(QWEN25_7B, "TPU v5e", microbatch_tokens=8192)
    # ...unless a paged pool is sized; then it passes
    mode.check_hbm(
        QWEN25_7B,
        "TPU v5e",
        microbatch_tokens=8192,
        decode_pool_tokens=256 * 1024,
    )
    # a 7B trainer on ONE chip is a train-half rejection
    with pytest.raises(AllocationValidationError, match="train half"):
        AllocationMode.from_str("jax:d4t4+d1t1").check_hbm(
            QWEN25_7B, "TPU v5e", microbatch_tokens=8192
        )


def test_zero1_opt_state_pricing():
    """ZeRO-1 (params replicated, moments dp-sharded) must price the opt
    state at 1/dp of the replicated bill and surface the freed bytes."""
    rep = hbm.estimate_train_hbm(
        QWEN25_7B, dp=8, tp=4, microbatch_tokens=8192, fsdp=False
    )
    z1 = hbm.estimate_train_hbm(
        QWEN25_7B, dp=8, tp=4, microbatch_tokens=8192, fsdp=False, zero1=True
    )
    # params/grads identical (still replicated over dp) ...
    assert z1.params_bytes == rep.params_bytes
    assert z1.grads_bytes == rep.grads_bytes
    # ... but the f32 moments divide by dp, and the delta is reported
    assert rep.opt_bytes == 8 * z1.opt_bytes
    assert z1.opt_freed_bytes == rep.opt_bytes - z1.opt_bytes
    assert "zero1_freed_gib" in z1.breakdown()
    assert "zero1_freed_gib" not in rep.breakdown()
    # the fsdp default (dp-sharded everything) is unchanged by the flag
    fs = hbm.estimate_train_hbm(QWEN25_7B, dp=8, tp=4, microbatch_tokens=8192)
    assert fs.opt_bytes == z1.opt_bytes and fs.opt_freed_bytes == 0


def test_interleaved_stash_pricing():
    """The 1f1b stash prices (2*pp-1) stage inputs; interleaved multiplies
    by v: v*(2*pp-1) virtual-chunk inputs, each a full [T_local, d] slab."""
    kw = dict(dp=2, tp=2, pp=2, microbatch_tokens=8192)
    plain = hbm.estimate_train_hbm(QWEN25_7B, **kw)
    inter = hbm.estimate_train_hbm(
        QWEN25_7B, pipeline_schedule="1f1b_interleaved", virtual_pp=2, **kw
    )
    gpipe = hbm.estimate_train_hbm(
        QWEN25_7B, pipeline_schedule="gpipe", **kw
    )
    t_local = 8192 // 2
    entry = t_local * QWEN25_7B.hidden_size * 2  # bf16
    assert plain.stash_bytes == 3 * entry  # 2*pp-1 = 3
    assert inter.stash_bytes == 2 * plain.stash_bytes
    assert gpipe.stash_bytes == 0
    assert inter.total_bytes - plain.total_bytes == plain.stash_bytes
    # no pipeline, no stash
    flat = hbm.estimate_train_hbm(QWEN25_7B, dp=4, microbatch_tokens=8192)
    assert flat.stash_bytes == 0 and "stash_gib" in flat.breakdown()


QWEN25_15B = ModelConfig(
    vocab_size=151936,
    hidden_size=1536,
    intermediate_size=8960,
    num_hidden_layers=28,
    num_attention_heads=12,
    num_key_value_heads=2,
    tie_word_embeddings=True,
    dtype="bfloat16",
    param_dtype="bfloat16",
)
V5E = 16 * hbm.GiB


def _trainer_resident(cfg, dp: int) -> int:
    """What the trainer holds of `cfg` a chip from step to step as the
    benchmark's cells run it: bf16 parameters, float32 gradient accumulator,
    AdamW's float32 mu and bf16 nu, all sharded over dp."""
    return hbm.param_count(cfg) * (2 + 4 + 4 + 2) // dp


def _room(cfg, tokens: int, dp: int, capacity: int = V5E) -> tuple[int, int]:
    est = hbm.estimate_train_hbm(cfg, dp=dp, microbatch_tokens=tokens)
    step = est.activation_bytes + est.logits_bytes + est.grad_transient_bytes
    resident = _trainer_resident(cfg, dp)
    return hbm.train_room_bytes(capacity, resident, step), resident + step


@pytest.mark.parametrize(
    "cfg,tokens,dp",
    [(QWEN25_05B, 8192, 1), (QWEN25_15B, 16384, 4)],
    ids=["train-0.5b-gsm8k", "train-1.5b-fsdp4"],
)
def test_remat_choice_keeps_something_at_the_train_cells_shapes(cfg, tokens, dp):
    """At the cells' largest micro-batches on 16 GiB chips the chosen set is
    not empty, and with the resident state and the step's own bytes it stays
    inside the margin."""
    room, held = _room(cfg, tokens, dp)
    n, kept = hbm.choose_remat_kept(cfg, tokens // dp, room, ring_steps=dp)
    assert n >= 1 and kept == hbm.remat_kept_bytes(cfg, tokens // dp, n, ring_steps=dp)
    assert 0 < kept <= room
    assert held + kept <= hbm.REMAT_ROOM_MARGIN * V5E


def test_check_fit_counts_a_backwards_transients():
    """`total_bytes` now holds `grad_transient_bytes` (a backward's gradients
    before accumulation, the head's float32 gradient, the gathered table
    under fsdp: XLA:TPU's plan has them, the old closed form did not), so a
    verdict of `check_fit` / `AllocationMode.check_hbm` can move: 7B over 8
    v5e chips was passed at 10.97 GiB a chip and is refused at 15.79; over
    16 it fits either way."""
    import dataclasses

    from areal_tpu.api.alloc_mode import AllocationMode, AllocationValidationError

    kind = "TPU v5 lite"
    over8 = hbm.estimate_train_hbm(QWEN25_7B, dp=8, microbatch_tokens=8192)
    n = hbm.param_count(QWEN25_7B)
    head = QWEN25_7B.vocab_size * QWEN25_7B.hidden_size
    assert over8.grad_transient_bytes == n * 2 // 8 + head * 4 + head * 2
    before = dataclasses.replace(over8, grad_transient_bytes=0)
    assert over8.total_bytes - before.total_bytes == over8.grad_transient_bytes
    assert round(before.total_bytes / hbm.GiB, 2) == 10.97
    assert round(over8.total_bytes / hbm.GiB, 2) == 15.79
    hbm.check_fit(before, kind)
    with pytest.raises(MemoryError, match="15.79 GiB/chip"):
        hbm.check_fit(over8, kind)
    with pytest.raises(AllocationValidationError, match="train half"):
        AllocationMode.from_str("jax:d1t8+d8").check_hbm(
            QWEN25_7B, kind, decode_context=4096)
    report = AllocationMode.from_str("jax:d1t8+d16").check_hbm(
        QWEN25_7B, kind, decode_context=4096)
    assert report["train"]["grad_transient_gib"] == round(
        (n * 2 // 16 + head * 6) / hbm.GiB, 3)
    # one chip, no fsdp gather: the table is not counted twice
    one = hbm.estimate_train_hbm(QWEN25_05B, microbatch_tokens=8192)
    head05 = QWEN25_05B.vocab_size * QWEN25_05B.hidden_size
    assert one.grad_transient_bytes == hbm.param_count(QWEN25_05B) * 2 + head05 * 4


def test_remat_choice_keeps_nothing_where_the_state_fills_the_chip():
    """1.5B whole on one chip: 18.5 GB of trainer state leave no room."""
    room, _ = _room(QWEN25_15B, 8192, 1)
    assert room < 0
    assert hbm.choose_remat_kept(QWEN25_15B, 8192, room) == (0, 0)
    assert hbm.REMAT_SETS[0] == ()


@pytest.mark.parametrize("cfg", [QWEN25_05B, QWEN25_15B], ids=["0.5b", "1.5b"])
def test_less_room_never_keeps_more(cfg):
    """Over every room from none to the whole chip the chosen count never
    falls as the room grows, each set is reached, and the sets are ordered:
    a later one holds the earlier one's names and more bytes."""
    tokens = 4096
    seen = []
    for room in range(-hbm.GiB, V5E, 64 * 1024 * 1024):
        n, kept = hbm.choose_remat_kept(cfg, tokens, room)
        assert kept <= max(room, 0)
        seen.append(n)
    assert seen == sorted(seen) and set(seen) == {0, 1, 2}
    sizes = [hbm.remat_kept_bytes(cfg, tokens, n) for n in range(len(hbm.REMAT_SETS))]
    assert sizes[0] == 0 and sizes == sorted(sizes) and len(set(sizes)) == 3
    for a, b in zip(hbm.REMAT_SETS, hbm.REMAT_SETS[1:]):
        assert set(a) < set(b)
    # a ring keeps every step's partial output: more bytes a token
    assert hbm.remat_kept_bytes(cfg, tokens, 1, ring_steps=4) > sizes[1]


def test_kept_bytes_follow_the_layers_structure():
    """A layer keeps what the arithmetic can size: a routed MLP adds nothing
    to the second set, a linear or latent mixer only the residual."""
    import dataclasses

    T = 1024
    dense = [hbm.remat_kept_bytes(QWEN25_05B, T, n) for n in (1, 2)]
    sparse_cfg = dataclasses.replace(
        QWEN25_05B, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=512)
    sparse = [hbm.remat_kept_bytes(sparse_cfg, T, n) for n in (1, 2)]
    assert sparse[0] == dense[0] and sparse[1] == sparse[0] < dense[1]
    first_dense = dataclasses.replace(sparse_cfg, first_k_dense=1)
    one_layer_mlp = 2 * QWEN25_05B.intermediate_size * 2 * T
    assert hbm.remat_kept_bytes(first_dense, T, 2) == sparse[0] + one_layer_mlp
    latent = dataclasses.replace(QWEN25_05B, kv_lora_rank=64)
    residual_only = QWEN25_05B.num_hidden_layers * QWEN25_05B.hidden_size * 2 * T
    assert hbm.remat_kept_bytes(latent, T, 1) == residual_only


@pytest.mark.parametrize("sets", [1, 2], ids=["attention", "attention_mlp"])
def test_kept_bytes_match_xla_memory_analysis(sets):
    """What XLA plans for the gradient of a small stack grows, from full
    recompute to each kept set, by the closed form's bytes (the tolerance of
    the 7B plan's cross-check: a factor of two either way)."""
    import dataclasses

    import jax.numpy as jnp

    from areal_tpu.models.qwen2 import forward

    cfg = dataclasses.replace(
        TINY, hidden_size=128, intermediate_size=512, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, remat=True, attn_impl="dense")
    T = 1024
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((T,), jnp.int32)

    def temp(kept):
        def step(p, ids, pos, seg):
            return jax.grad(
                lambda p: forward(p, ids, pos, seg, cfg, remat_kept=kept).sum())(p)
        ma = jax.jit(step).lower(params, ints, ints, ints).compile().memory_analysis()
        if ma is None or not ma.temp_size_in_bytes:
            pytest.skip("this backend plans no temporaries")
        return ma.temp_size_in_bytes

    grown = temp(hbm.REMAT_SETS[sets]) - temp(hbm.REMAT_SETS[0])
    closed = hbm.remat_kept_bytes(cfg, T, sets)
    assert 0.5 < grown / closed < 2.0, (grown, closed)


def test_device_kind_spellings():
    """GKE-style v5e spellings must not fall through to the v5p row."""
    for kind in ("TPU v5 lite", "tpu-v5-lite-podslice", "v5litepod", "V5E"):
        assert hbm.hbm_bytes(kind) == 16 * hbm.GiB, kind
    assert hbm.hbm_bytes("TPU v5p") == 95 * hbm.GiB
    assert hbm.hbm_bytes("TPU v5") == 95 * hbm.GiB
    from areal_tpu.utils.flops import peak_flops

    assert peak_flops("tpu-v5-lite-podslice") == 197e12
    assert peak_flops("TPU v5") == 459e12


def test_unknown_device_kind_is_an_error():
    """No made-up capacity or peak for a device the tables do not list."""
    from areal_tpu.utils.flops import peak_flops

    for kind in ("cpu", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match="device kind"):
            hbm.hbm_bytes(kind)
        with pytest.raises(ValueError, match="device kind"):
            peak_flops(kind)


def test_decode_paged_pool_vs_dense():
    """The paged pool's reservation is the knob: 64 slots x 32k dense
    reserves ~2M KV rows; a 256k-token pool is 8x smaller, and the
    estimator prices exactly that difference."""
    dense = hbm.estimate_decode_hbm(QWEN25_7B, tp=4, slots=64)
    paged = hbm.estimate_decode_hbm(QWEN25_7B, tp=4, pool_tokens=256 * 1024)
    assert dense.kv_bytes == 8 * paged.kv_bytes
    with pytest.raises(MemoryError):
        hbm.check_fit(dense, "TPU v5e")
    hbm.check_fit(paged, "TPU v5e")


@pytest.mark.slow
def test_full_depth_7b_plan_compiles(cpu_devices):
    """Full-geometry Qwen2.5-7B (depth 28, width 3584, vocab 152064) on the
    documented d4t2 mesh: the ENTIRE sharded grad step + optimizer update
    compiles to an XLA program on the CPU host, no parameters materialized.
    This is the "prove the program builds" half of a real-scale story that
    tiny-geometry dryruns cannot give."""
    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.engine.sft.lm_engine import JaxLMEngine

    cfg7 = dataclasses_replace_scan(QWEN25_7B)
    eng = JaxLMEngine(
        TrainEngineConfig(
            experiment_name="plan",
            trial_name="7b",
            path="",
            init_from_scratch=True,
            dtype="bfloat16",
            mb_spec=MicroBatchSpec(max_tokens_per_mb=8192),
            optimizer=OptimizerConfig(
                lr=1e-5,
                warmup_steps_proportion=0.0,
                lr_scheduler_type="constant",
                gradient_clipping=1.0,
            ),
            gradient_checkpointing=True,
        )
    )
    eng.model_config = cfg7
    eng.create_process_group(
        ParallelStrategy(data_parallel_size=4, tensor_parallel_size=2)
    )
    try:
        report = eng.plan_compile_check(mb_tokens=8192)
        assert "grad_step" in report and "apply_update" in report
        ma = report["apply_update"]
        if ma.get("argument_size_in_bytes"):
            # params bf16 + grads f32 + opt f32 moments, dp*tp-sharded:
            # the arguments alone should land within 2x of the closed-form
            # estimate's static terms (cross-check estimator vs XLA)
            est = hbm.estimate_train_hbm(
                QWEN25_7B, dp=4, tp=2, microbatch_tokens=8192
            )
            static = est.params_bytes + est.opt_bytes + 2 * est.grads_bytes
            assert 0.5 < ma["argument_size_in_bytes"] / static < 2.0, (
                ma,
                est.breakdown(),
            )
    finally:
        eng.destroy()


def dataclasses_replace_scan(cfg):
    import dataclasses

    # remat with nothing kept (`hbm.REMAT_SETS[0]`): `forward`'s default
    return dataclasses.replace(cfg, scan_layers=True, remat=True)
