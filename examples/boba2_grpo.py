"""boba² — the north-star recipe: async GRPO math RL on a 7B reasoning
model across a v5p pod slice.

Parity: the reference's boba² release (/root/reference/blog/AReaL_v0_3.md:
183-186 — 7B math RL with fully asynchronous rollout, decoupled PPO loss,
staleness η=4, group sampling) and its runnable math entry
(/root/reference/examples/math/ + recipe yaml). TPU differences:

- The allocation string carves ONE pod slice into decode servers + GSPMD
  trainer: ``jax:d16t4+d16t4`` = 64 v5p chips serving rollouts (16 engines
  x tp4) + 64 chips training (fsdp-dp16 x tp4). XLA collectives over ICI
  replace the reference's NCCL groups; weight pushes ride the DCN
  framed-bucket path (core/weight_transfer.py).
- ``--plan-check`` validates the WHOLE plan on any host before touching a
  chip: closed-form HBM accounting for both halves
  (AllocationMode.check_hbm) plus an AOT compile of the full-depth sharded
  train program (JaxTrainEngine.plan_compile_check) — run it on a laptop
  with N virtual CPU devices to prove the v5p program builds.

Usage:

  # validate the 7B plan without hardware (any machine):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=64 \\
      python examples/boba2_grpo.py --config examples/configs/boba2_7b_grpo.yaml \\
      --plan-check

  # launch on the pod slice (launcher spawns decode servers + trainer):
  python -m areal_tpu.launcher.local examples/boba2_grpo.py \\
      --config examples/configs/boba2_7b_grpo.yaml

  # offline tiny-geometry smoke of the same loop (CPU, synthetic data):
  python examples/boba2_grpo.py --config examples/configs/boba2_7b_grpo.yaml \\
      +smoke (see tests/test_examples_smoke.py for the override set)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Known 7B-class tensor geometries, keyed by the tail of the model path.
# The plan check must work on machines with no checkpoint and no network
# (ModelConfig.from_hf_config needs local files), so the recipe carries the
# geometry of its target models explicitly.
_GEOMETRIES = {
    "qwen2.5-7b": dict(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_hidden_layers=28,
        num_attention_heads=28,
        num_key_value_heads=4,
        tie_word_embeddings=False,
    ),
    # R1-Distill-Qwen-7B shares the Qwen2.5-7B geometry
    "deepseek-r1-distill-qwen-7b": dict(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_hidden_layers=28,
        num_attention_heads=28,
        num_key_value_heads=4,
        tie_word_embeddings=False,
    ),
}


def _target_model_config(config):
    """ModelConfig for the recipe's model: from the local checkpoint when
    present, else from the carried geometry table."""
    from areal_tpu.models.qwen2 import ModelConfig

    path = config.actor.path
    if path and os.path.isdir(path):
        return ModelConfig.from_hf_config(
            path, dtype=config.actor.dtype, param_dtype=config.actor.dtype
        )
    key = (path or "").split("/")[-1].lower()
    for name, geom in _GEOMETRIES.items():
        if name in key:
            return ModelConfig(
                dtype=config.actor.dtype,
                param_dtype=config.actor.dtype,
                scan_layers=True,
                remat=config.actor.gradient_checkpointing,
                **geom,
            )
    raise SystemExit(
        f"--plan-check: no local checkpoint at {path!r} and no carried "
        f"geometry matches; add one to _GEOMETRIES"
    )


def plan_check(argv) -> None:
    """Validate HBM fit for both allocation halves and AOT-compile the
    full-depth sharded train program. Exits 0 iff the plan is launchable."""
    import jax

    from areal_tpu.api.alloc_mode import AllocationMode
    from areal_tpu.api.cli_args import GRPOConfig, load_expr_config

    config, _ = load_expr_config(argv, GRPOConfig)
    alloc = AllocationMode.from_str(config.allocation_mode)
    model_cfg = _target_model_config(config)
    device_kind = os.environ.get("AREAL_PLAN_DEVICE", "TPU v5p")

    report = alloc.check_hbm(
        model_cfg,
        device_kind,
        microbatch_tokens=config.actor.mb_spec.max_tokens_per_mb,
        remat=config.actor.gradient_checkpointing,
        decode_slots=config.decode.max_running_requests,
        decode_context=config.decode.context_length,
        decode_pool_tokens=config.decode.kv_pool_tokens,
    )
    print(f"[plan-check] HBM fit on {device_kind!r}: OK")
    for half, bd in report.items():
        print(f"[plan-check]   {half}: {bd}")

    train = alloc.train
    need = train.world_size
    have = len(jax.devices())
    if have < need:
        print(
            f"[plan-check] {need} devices required for the AOT compile but "
            f"only {have} present — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} (CPU is fine); "
            "skipping compile step"
        )
        return
    from areal_tpu.engine.sft.lm_engine import JaxLMEngine

    eng = JaxLMEngine(config.actor)
    eng.model_config = model_cfg
    eng.create_process_group(train)
    try:
        ma = eng.plan_compile_check(
            mb_tokens=config.actor.mb_spec.max_tokens_per_mb
        )
        print(f"[plan-check] full-depth train program compiled: {ma}")
    finally:
        eng.destroy()
    print("[plan-check] PASS")


def main(argv):
    if "--plan-check" in argv:
        plan_check([a for a in argv if a != "--plan-check"])
        return
    # The training loop IS the async-GRPO loop: prepare_batch keeps >=2
    # batches in flight against the decode servers, staleness-gated by
    # max_head_offpolicyness (η), with the decoupled behav/prox loss.
    from gsm8k_grpo import main as grpo_main

    grpo_main(argv)


if __name__ == "__main__":
    if "--plan-check" in sys.argv[1:]:
        main(sys.argv[1:])
    else:
        from areal_tpu.utils.experiment import run_with_status

        run_with_status(main, sys.argv[1:])
