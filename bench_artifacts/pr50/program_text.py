"""usage: JAX_PLATFORMS=cpu python bench_artifacts/pr50/program_text.py [--root <tree>] [--kind <k> ...]
PR 44's `program_text.py` over EVERY kind of configuration the benchmark has: each program a tiny decode
engine makes over a group of same-prompt requests, a wave of two distinct prompts and a request with no
prefill (chunk, batched prefill, patch, the copies of a fork), and the trainer's `forward`, by name, with
the sha256 of its lowered text WITHOUT locations, on the CPU. `program_text.sh` runs it on the parent
(`_parent/`) and on this tree and diffs: a line that differs is a changed program. The tiny models are the
tests' own: a dense stack, OLMoE's experts, K-EXAONE's ring, Qwen3-Next's state, DeepSeek-V2's latent rows,
Kimi-Linear's state beside latent rows, SDAR's blocks."""
import argparse
import hashlib
import os
import sys

KINDS = {"uniform": None, "moe": ("test_olmoe", "TINY"), "ring": ("test_kexaone", "FULL"),
         "state": ("test_qwen3next", "FULL"), "latent": ("test_deepseek_v2", "FULL"),
         "state_latent": ("test_kimi_linear", "FULL"), "block": ("test_sdar", "CFG")}
ap = argparse.ArgumentParser()
ap.add_argument("--root", default=".")
ap.add_argument("--kind", nargs="*", default=list(KINDS))
args = ap.parse_args()
root = os.path.abspath(args.root)
sys.path[:0] = [root, os.path.join(root, "tests")]
os.chdir(root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

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
from areal_tpu.models import qwen2  # noqa: E402
from benchmark.lib import weights  # noqa: E402


def one(kind):
    texts.clear()
    if KINDS[kind] is None:
        cfg = qwen2.ModelConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                                num_attention_heads=4, num_key_value_heads=2, dtype="float32",
                                param_dtype="float32")
    else:
        mod, name = KINDS[kind]
        cfg = getattr(__import__(mod), name)
    params = weights.seeded_params(cfg, 7)
    eng = JaxDecodeEngine(
        JaxDecodeConfig(context_length=256, max_running_requests=4, new_tokens_per_chunk=8, page_size=4,
                        dtype="float32", kv_cache_dtype="float32"), InferenceEngineConfig())
    eng.set_model(params, cfg)
    eng.initialize()
    try:
        import asyncio

        async def wave(prompts):
            eng.pause_generation()
            tasks = [asyncio.ensure_future(eng.agenerate(ModelRequest(
                input_ids=p, gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=12))))
                for p in prompts]
            await asyncio.sleep(0)
            eng.continue_generation()
            return await asyncio.gather(*tasks)

        asyncio.run(wave([[1, 5, 9, 13, 2, 7, 3]] * 3))   # 6 rows: a partial boundary block
        asyncio.run(wave([[2, 6, 10, 8, 4], [4, 3, 9, 11, 12, 6]]))  # two distinct prompts: a wave of 2
        asyncio.run(wave([[3]]))  # no prefill: a state goes back to zero
    finally:
        eng.destroy()
    T = 24
    fwd = real_jit(lambda p, i: qwen2.forward(p, i, jnp.arange(T), jnp.zeros(T, jnp.int32), cfg))
    texts["forward"] = {hashlib.sha256(
        fwd.lower(params, jnp.zeros(T, jnp.int32)).as_text().encode()).hexdigest()[:16]}
    for name in sorted(texts):
        print(kind, name, " ".join(sorted(texts[name])), flush=True)


for kind in args.kind:
    one(kind)
