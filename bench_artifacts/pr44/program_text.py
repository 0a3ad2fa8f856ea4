"""usage: JAX_PLATFORMS=cpu python bench_artifacts/pr44/program_text.py [--root <tree>]
Every program a tiny decode engine makes over a group of same-prompt requests and a second wave (chunk,
batched prefill, patch, the fork's block copy; with --kind ring/state also the ring and state copies), by
name, with the sha256 of its lowered text WITHOUT locations, on the CPU (a Pallas kernel is interpreted
here: the text around it is what is compared; `ops/` is not this PR's). Run it on two trees and diff the
output: a line that differs is a changed program."""
import argparse
import hashlib
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=".")
ap.add_argument("--kind", default="uniform", choices=["uniform", "ring", "state", "latent"])
args = ap.parse_args()
root = os.path.abspath(args.root)
sys.path[:0] = [root, os.path.join(root, "tests")]
os.chdir(root)

import jax  # noqa: E402

real_jit = jax.jit
texts: dict[str, set] = {}


def spy(fn, **kw):
    jitted = real_jit(fn, **kw)
    name = getattr(fn, "__name__", "?")

    class Program:
        def __call__(self, *a, **k):
            text = jitted.lower(*a, **k).as_text()
            texts.setdefault(name, set()).add(hashlib.sha256(text.encode()).hexdigest()[:16])
            return jitted(*a, **k)

        def __getattr__(self, attr):
            return getattr(jitted, attr)

    return Program()


jax.jit = spy

from areal_tpu.api.cli_args import GenerationHyperparameters, InferenceEngineConfig, JaxDecodeConfig  # noqa: E402
from areal_tpu.api.io_struct import ModelRequest  # noqa: E402
from areal_tpu.engine.jax_decode import JaxDecodeEngine  # noqa: E402

if args.kind == "uniform":
    from areal_tpu.models.qwen2 import ModelConfig, init_params

    cfg = ModelConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
else:
    mod = __import__({"ring": "test_kexaone", "state": "test_qwen3next", "latent": "test_deepseek_v2"}[args.kind])
    cfg = mod.FULL
    from benchmark.lib import weights

    params = weights.seeded_params(cfg, 7)

eng = JaxDecodeEngine(
    JaxDecodeConfig(context_length=256, max_running_requests=4, new_tokens_per_chunk=8, page_size=4,
                    dtype="float32", kv_cache_dtype="float32"), InferenceEngineConfig())
eng.set_model(params, cfg)
eng.initialize()
try:
    import asyncio

    async def group(prompt, n):
        eng.pause_generation()
        tasks = [asyncio.ensure_future(eng.agenerate(ModelRequest(
            input_ids=prompt, gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=12)))) for _ in range(n)]
        await asyncio.sleep(0)
        eng.continue_generation()
        return await asyncio.gather(*tasks)

    asyncio.run(group([1, 5, 9, 13, 2, 7, 3], 3))   # 6 rows: a partial boundary block
    asyncio.run(group([2, 6, 10, 8, 4], 2))
    asyncio.run(group([3], 1))  # no prefill: a state goes back to zero
finally:
    eng.destroy()
for name in sorted(texts):
    print(args.kind, name, " ".join(sorted(texts[name])))
