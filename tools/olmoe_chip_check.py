"""OLMoE at its published widths on the chip, outside the benchmark: what the
`rollout-olmoe-gsm8k` cell does not run. Not a rate; every phase prints one
JSON line and the script exits non-zero if a phase that must hold does not.

    chiprun -- python tools/olmoe_chip_check.py [--seed N] [--phases trainer,precision,engine]

trainer    the trainer half at depth 1 (8.8 GB at 14 B a parameter):
           `compute_logp` of the batch's first four packed sequences agrees
           with `benchmark/reference/olmoe_ref.py`; one `ppo_update` gives a
           finite loss, a grad-norm > 0 and a router kernel that moved. At
           depth 1 one layer's experts are most of the hidden state, so a
           near-tie at the eighth expert that bf16 and float32 settle
           differently moves that token by up to a nat: the mean is held on
           every token, the largest delta on the tokens whose routing the
           reference says is not a near-tie (margin >= NEAR_TIE).
precision  the two readings the reference's tolerances are set from, on the
           packed forward at depth 8: the program (bf16) against the
           reference (float32), and the reference itself with every weight
           rounded to 3 mantissa bits (float8_e4m3's, its exponent left
           wide: the mildest float8), the nearest precision below the
           configuration's bf16, which has to come out as not correct. Also
           the program with its router softmax in bf16 (reported, not held).
engine     prefill then paged decode through `JaxDecodeEngine` on a bf16 pool
           (must agree) and on an int8 pool (must not).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace
from unittest import mock

# relative gap between the 8th and 9th router probability under which bf16
# and float32 may route a token differently
NEAR_TIE = 0.05
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _say(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}, default=float), flush=True)


def _hf(registry, **over) -> dict:
    from benchmark.lib.harness import CONFIG_META_KEYS

    f = registry.cell("rollout-olmoe-gsm8k")["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def trainer(registry, seed: int) -> bool:
    import numpy as np

    from benchmark.lib import harness, kind_rollout_moe, kind_train
    from benchmark.lib.traffic import Traffic, batch_lengths, longest_sequence
    from benchmark.reference import olmoe_ref

    cell = registry.cell("train-0.5b-gsm8k")  # the trainer's settings, as the recipe has them
    cell["name"] = "olmoe-depth1-trainer-check"
    cell["config_file"] = _hf(registry, num_hidden_layers=1)
    cell["traffic_file"] = dict(cell["traffic_file"], groups_per_batch=1)
    rt = harness.Runtime(SimpleNamespace(seed=seed, seconds=1, trace=0), cell, registry,
                         time.monotonic())
    actor, _ = kind_train.build_actor(rt, harness.experiment_config(rt))
    tfile = cell["traffic_file"]
    batch = Traffic(tfile, actor.model_config.vocab_size, seed).train_batch(0, 1)
    prox = actor.compute_logp(batch)
    lens = batch_lengths(batch)
    checks = []
    for i in range(4):
        ref, margin = olmoe_ref.token_logprobs(
            actor.params, actor.model_config, batch["input_ids"][i, : lens[i]],
            temperature=float(actor.config.temperature), pad_to=longest_sequence(tfile),
            with_margins=True)
        c = kind_rollout_moe.compare_with_reference(
            f"trainer.compute_logp seq {i}", prox[i, : lens[i] - 1], ref)
        d = np.abs(prox[i, : lens[i] - 1] - ref)
        clear = margin >= NEAR_TIE
        c.update(near_tie_tokens=int((~clear).sum()), max_abs_clear=float(d[clear].max()),
                 max_abs_near_tie=float(d[~clear].max()) if (~clear).any() else 0.0)
        c["ok"] = bool(c["mean_abs"] <= olmoe_ref.MEAN_ABS_TOL
                       and c["max_abs_clear"] <= olmoe_ref.MAX_ABS_TOL)
        checks.append(c)
    batch["logprobs"] = np.roll(prox, 1, axis=-1) * (np.asarray(batch["loss_mask"]) > 0)
    router0 = np.asarray(actor.params["layers"]["mlp"]["router_kernel"], np.float32)
    stats = kind_train.ppo_step(rt, actor, batch)
    router1 = np.asarray(actor.params["layers"]["mlp"]["router_kernel"], np.float32)
    bad = harness.finite_steps([stats])
    moved = float(np.abs(router1 - router0).max())
    ok = all(c["ok"] for c in checks) and not bad and moved > 0
    _say("trainer", ok=ok, layers=1, sequences=len(lens), tokens=int(sum(lens)), checks=checks,
         not_finite=bad, router_kernel_max_change=moved, minibatches=stats,
         memory_peak_bytes=harness.device_line()["memory_peak_bytes"])
    actor.destroy()
    return ok


def _forward_logprobs(params, cfg, ids):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models.qwen2 import forward

    T = len(ids)
    logits = jax.jit(lambda p, i: forward(p, i, jnp.arange(T), jnp.zeros(T, jnp.int32), cfg))(
        params, jnp.asarray(ids))
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


def precision(registry, seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models.qwen2 import ModelConfig
    from benchmark.lib import kind_rollout_moe, weights
    from benchmark.reference import olmoe_ref

    cfg = ModelConfig.from_hf_config(_hf(registry), dtype="bfloat16", param_dtype="bfloat16")
    params = weights.seeded_params(cfg, seed)
    rng = np.random.default_rng(seed)
    out = {}
    seqs = [rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in (1024, 640)]
    refs = [olmoe_ref.token_logprobs(params, cfg, s) for s in seqs]
    cmp = kind_rollout_moe.compare_with_reference
    out["program_bf16"] = [cmp(f"forward {len(s)}", _forward_logprobs(params, cfg, s), r)
                           for s, r in zip(seqs, refs)]
    real = jax.nn.softmax

    def bf16_softmax(x, axis=-1, **kw):
        if x.ndim == 2 and x.shape[-1] == cfg.num_experts:
            return real(x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32)
        return real(x, axis=axis, **kw)

    with mock.patch.object(jax.nn, "softmax", bf16_softmax):
        out["program_bf16_router_softmax_in_bf16"] = [
            cmp(f"forward {len(s)}", _forward_logprobs(params, cfg, s), r)
            for s, r in zip(seqs, refs)]
    # in place: the chip has no room for a second copy of the weights
    params = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), 8, 3).astype(a.dtype), p),
        donate_argnums=0)(params)
    out["reference_with_3_mantissa_bit_weights"] = [
        cmp(f"reference {len(s)}", olmoe_ref.token_logprobs(params, cfg, s), r)
        for s, r in zip(seqs, refs)]
    ok = (all(c["ok"] for c in out["program_bf16"])
          and not any(c["ok"] for c in out["reference_with_3_mantissa_bit_weights"]))
    _say("precision", ok=ok, tolerances=[olmoe_ref.MEAN_ABS_TOL, olmoe_ref.MAX_ABS_TOL], **out)
    return ok


def engine(registry, seed: int) -> bool:
    import numpy as np

    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import ModelConfig
    from benchmark.lib import kind_rollout, kind_rollout_moe, weights

    cfg = ModelConfig.from_hf_config(_hf(registry), dtype="bfloat16", param_dtype="bfloat16")
    params = weights.seeded_params(cfg, seed)
    prompt = np.random.default_rng(seed).integers(1, cfg.vocab_size, 200).tolist()
    out = {}
    for kv in ("bfloat16", "int8"):
        eng = JaxDecodeEngine(JaxDecodeConfig(
            context_length=1280, max_running_requests=8, new_tokens_per_chunk=128, page_size=128,
            dtype="bfloat16", kv_cache_dtype=kv))
        eng.set_model(params, cfg)
        eng.initialize()
        try:
            resp = eng.generate(kind_rollout._request(prompt, 600, 1.0), 900.0)
            done = [{"resp": resp, "want": 600, "group": 0}]
            (out[kv],) = kind_rollout_moe.check_decode(None, eng, done, 1, pad_to=1280)
        finally:
            eng.destroy()
        gc.collect()
    ok = out["bfloat16"]["ok"] and not out["int8"]["ok"]
    _say("engine", ok=ok, **out)
    return ok


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--phases", default="trainer,precision,engine")
    args = ap.parse_args(argv)
    import jax

    from areal_tpu.platforms import enable_compilation_cache
    from benchmark.lib.registry import Registry

    if jax.devices()[0].platform != "tpu":
        print(f"olmoe_chip_check needs a TPU, found {jax.devices()[0].platform!r}", file=sys.stderr)
        return 2
    enable_compilation_cache()
    registry = Registry(ROOT)
    results = {}
    for name in args.phases.split(","):
        results[name] = {"trainer": trainer, "precision": precision, "engine": engine}[name](
            registry, args.seed)
        gc.collect()
    _say("all", **results)
    return 0 if all(results.values()) else 1


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)  # engine threads must not keep a finished run alive
