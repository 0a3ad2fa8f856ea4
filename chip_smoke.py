"""Quickest proof that the system still starts on the chip.

One process, one command, no network:

    python chip_smoke.py

1. Kernel phase (tools/tpu_smoke.py): every Pallas kernel under
   areal_tpu/ops compiled by Mosaic at Qwen2.5-0.5B's shapes and compared
   against the op's own XLA implementation.
2. Loop phase: the colocated asynchronous GRPO loop through the normal entry
   point (`examples/gsm8k_grpo.py`'s `main`) at the full width AND depth of
   Qwen2.5-0.5B (examples/configs/qwen2.5_0.5b_grpo_smoke.yaml): decode
   rollouts -> RLVR reward -> staleness gate -> decoupled-PPO update ->
   in-memory weight push -> next rollouts on the new weights. Weights come
   from a seed, prompts and rewards from the synthetic arithmetic dataset.

It fails (exit code != 0, no result line) unless JAX's device is a TPU, every
step's loss and grad-norm are finite, every step trained on generated tokens,
the decode engine's logprobs agree with the trainer's recomputation, the
decode engine ends on weight version == steps, and the loaded train-step and
decode-chunk programs each contain a Mosaic custom call. A number it prints
is a smoke run's set-up or wall time, never a throughput.

The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""

from __future__ import annotations

import faulthandler
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(REPO, "examples", "configs", "qwen2.5_0.5b_grpo_smoke.yaml")
MODEL_DIR = os.path.join(REPO, "examples", "configs", "qwen2.5-0.5b")
# a hang is a failure too: past this, dump every thread's stack and exit.
# A whole run is 740 s on one chip (the kernel phase 600); on a four-chip
# host that compiled 1.6 times slower the kernel phase alone passed 1,080 s
# and a limit of 1,100 cut the loop phase at its first compile (PR 29).
WATCHDOG_S = 2000


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    print(f"  ok: {what}", flush=True)


def cache_counters() -> dict[str, int]:
    """Count persistent-compile-cache requests, hits and misses as JAX
    itself reports them."""
    import jax.monitoring

    counts = {"requests": 0, "hits": 0, "misses": 0}
    names = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def on_event(event: str, **kwargs) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def loaded_program_text(client, module_name: str) -> str:
    """Optimized HLO of the executables loaded on the device whose module
    is `module_name` — what actually runs, cache hit or not."""
    texts = []
    for exe in client.live_executables():
        for mod in exe.hlo_modules():
            if mod.name == module_name:
                texts.append(mod.to_string())
    return "\n".join(texts)


def inspect_engines(actor, rollout, n_steps: int) -> None:
    """Called at the end of the last step with both engines still live."""
    import jax

    from areal_tpu.models.qwen2 import resolve_attn_impl
    from areal_tpu.ops.paged_attention import resolve_impl

    mc = actor.model_config
    print(
        f"model: hidden={mc.hidden_size} intermediate={mc.intermediate_size} "
        f"layers={mc.num_hidden_layers} heads={mc.num_attention_heads}/"
        f"{mc.num_key_value_heads} head_dim={mc.head_dim_} vocab={mc.vocab_size} "
        f"tied={mc.tie_word_embeddings} dtype={mc.dtype} scan_layers={mc.scan_layers}"
    )
    check(
        (mc.hidden_size, mc.intermediate_size, mc.num_hidden_layers,
         mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim_,
         mc.vocab_size, mc.dtype)
        == (896, 4864, 24, 14, 2, 64, 151936, "bfloat16"),
        "Qwen2.5-0.5B geometry, nothing cut, bf16",
    )
    check(
        actor.get_version() == n_steps and rollout.get_version() == n_steps,
        f"trainer and decode weight version == steps == {n_steps}",
    )

    def placed(name, x):
        x = jax.tree.leaves(x)[0]  # an int8-served kernel is {"q", "scale"}
        ids = sorted(d.id for d in x.sharding.device_set)
        print(f"{name} {x.shape} on device(s) {ids}: {x.sharding}")

    print(f"trainer mesh: {dict(actor.mesh.shape)}")
    placed("trainer param leaf", actor.params["layers"]["mlp"]["gate_kernel"])
    placed("decode  param leaf", rollout.params["layers"]["mlp"]["gate_kernel"])
    placed("decode  KV pool   ", rollout._k_cache)

    attn = resolve_attn_impl(mc)
    paged = resolve_impl(rollout.config.paged_attn_impl)
    want_attn = "flash" if actor.mesh.size == 1 else "ring"
    check(attn == want_attn, f"trainer attention resolves to {attn!r}")
    check(paged == "pallas", f"decode paged attention resolves to {paged!r}")
    # not the flags: the programs the device actually holds
    client = jax.devices()[0].client
    for program, module in (("train step", "jit_grad_step"),
                            ("decode chunk", "jit_chunk")):
        n = loaded_program_text(client, module).count("tpu_custom_call")
        check(n > 0, f"{program} ({module}) holds {n} Mosaic custom call(s): "
                     "compiled kernel, interpret=False")


def main() -> int:
    t_start = time.monotonic()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform != "tpu":
        print(
            f"chip_smoke: found no TPU (JAX reports {device}); this check "
            "only passes on the chip",
            file=sys.stderr,
        )
        return 1

    import jaxlib

    from areal_tpu.platforms import _DEFAULT_CACHE_DIR, enable_compilation_cache

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "unknown"
    enable_compilation_cache()
    # on the chip every compile is worth keeping, however short: a call
    # starts with whatever the cache directory holds and nothing else
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counts = cache_counters()
    print(f"device: {device}  process: pid={os.getpid()}")
    print(f"versions: python={sys.version.split()[0]} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}")
    print("compile cache: "
          + (os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR))

    # -- phase 1: kernels ------------------------------------------------
    print("== kernel phase (tools/tpu_smoke.py) ==", flush=True)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tpu_smoke

    t0 = time.monotonic()
    verdicts = tpu_smoke.run_all()
    kernel_s = time.monotonic() - t0
    bad = [v["kernel"] for v in verdicts if not v["ok"]]
    check(not bad, f"all {len(verdicts)} kernels lowered and match ({bad or 'none'} failed)")

    # -- phase 2: the loop -----------------------------------------------
    print("== loop phase (examples/gsm8k_grpo.py main) ==", flush=True)
    sys.path.insert(0, os.path.join(REPO, "examples"))
    import gsm8k_grpo
    import numpy as np

    from areal_tpu.utils._native import load_datapack

    argv = ["--config", CONFIG, f"actor.path={MODEL_DIR}",
            f"cluster.fileroot={os.path.join(REPO, 'chiprun_out', 'chip_smoke')}"]
    argv += sys.argv[1:]  # key=value overrides, e.g. total_train_steps=6
    from areal_tpu.api.cli_args import GRPOConfig, load_expr_config

    config, _ = load_expr_config(argv, GRPOConfig)

    # (oldest, newest) weight version each step's trained tokens were
    # sampled under: the staleness the gate admitted
    sampled_under = []

    def after_step(global_step, batch, actor, rollout):
        # prompt tokens carry version -1, padding has no attention
        v = np.asarray(batch["versions"])
        v = v[(np.asarray(batch["attention_mask"]) > 0) & (v >= 0)]
        check(v.size > 0, f"step {global_step}: the batch holds generated tokens")
        sampled_under.append((int(v.min()), int(v.max())))
        if global_step == config.total_train_steps - 1:
            inspect_engines(actor, rollout, config.total_train_steps)

    check(
        config.async_training and config.decode.page_size == 128
        and config.decode.context_length >= 1024
        and config.gconfig.n_samples >= 4
        and config.gconfig.max_new_tokens >= 128
        and config.total_train_steps >= 3
        and config.decode.dtype == config.actor.dtype == "bfloat16",
        "sizes are real: async, bf16, page 128, context >= 1024, "
        ">= 4 samples, >= 128 new tokens, >= 3 steps",
    )
    t0 = time.monotonic()
    history = gsm8k_grpo.main(argv, after_step=after_step)
    loop_s = time.monotonic() - t0

    check(len(history) == config.total_train_steps,
          f"{len(history)} GRPO steps taken")
    step_s = []
    for i, minibatches in enumerate(history):
        head = minibatches[0]
        for mb in minibatches:
            loss, gnorm = mb["grpo_actor/loss"], mb["grpo_actor/grad_norm"]
            check(math.isfinite(loss) and math.isfinite(gnorm) and gnorm > 0,
                  f"step {i}: loss={loss:.5f} grad_norm={gnorm:.4f} finite")
            w = mb["grpo_actor/behave_imp_weight"]
            # the decode engine (paged Pallas attention) and the trainer
            # (flash attention) score the same tokens under the same weights
            check(abs(w - 1.0) < 0.05,
                  f"step {i}: decode logprobs agree with the trainer's "
                  f"recomputation (importance weight {w:.4f})")
        check(head["grpo_actor/n_valid_tokens"] > 0,
              f"step {i}: trained on {int(head['grpo_actor/n_valid_tokens'])} "
              f"generated tokens, sampled under weight versions "
              f"{sampled_under[i][0]}..{sampled_under[i][1]}")
        phases = {k[len("timeperf/"):]: v for k, v in head.items()
                  if k.startswith("timeperf/") and v >= 0.05}
        step_s.append(sum(v for k, v in head.items() if k.startswith("timeperf/")))
        print(f"  step {i} wall seconds: "
              + " ".join(f"{k}={v:.2f}" for k, v in phases.items()))
    pushed = max(newest for _, newest in sampled_under)
    check(pushed >= 1, f"rollouts were generated on pushed weights (up to v{pushed})")

    print(f"batches packed by: {'native libdatapack.so' if load_datapack() else 'numpy'}")
    print(f"compile cache: requests={counts['requests']} hits={counts['hits']} "
          f"misses={counts['misses']}")
    print("smoke run wall seconds (not a throughput): "
          f"kernel_phase={kernel_s:.1f} loop_setup_and_teardown="
          f"{loop_s - sum(step_s):.1f} first_step_with_compiles={step_s[0]:.1f} "
          f"later_steps={[round(s, 1) for s in step_s[1:]]} "
          f"total={time.monotonic() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — report, then leave at once
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine threads must not keep a finished (or failed) smoke alive
    os._exit(code)
