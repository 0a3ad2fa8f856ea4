"""Where the bf16 program departs from the float32 reference, at the published
widths on the chip (no engine, no cache): one seeded sequence through
`forward`'s own layer function a layer at a time beside the reference's, the
relative distance of the hidden state after every layer, then the
log-probabilities' mean and largest |difference| for a few variants of the
program's arithmetic (PROBE_VARIANTS, comma separated):

    bf16      the program as it is (dtype bfloat16)
    f32act    bf16 weights, float32 activations (dtype float32): what is left is order of summation
    uz32      the mixer's in_proj accumulated and kept in float32
    chiprun -- python bench_artifacts/pr50/precision_probe.py [tokens] [seed]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import ModelConfig  # noqa: E402
from benchmark.lib import kind_rollout_ssm, weights  # noqa: E402
from benchmark.reference import jamba_ref  # noqa: E402

T = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
SEED = int(sys.argv[2]) if len(sys.argv) > 2 else 5000000777
hf = json.load(open(os.path.join(ROOT, "benchmark/configs/ai21-jamba2-3b.json")))
hf = {k: v for k, v in hf.items() if k not in ("source", "reduced", "assumed", "deployment", "parameters")}
if os.environ.get("PROBE_TINY"):
    hf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=14, vocab_size=512,
              num_attention_heads=4, mamba_dt_rank=8)
cfg = ModelConfig.from_hf_config(hf, dtype="bfloat16", param_dtype="bfloat16", attn_impl="dense")
params = kind_rollout_ssm.redraw_mixer_leaves(weights.seeded_params(cfg, SEED), SEED)
ids = np.random.default_rng(SEED).integers(1, cfg.vocab_size, T).astype(np.int32)
ref_lp = jamba_ref.token_logprobs(params, cfg, ids)


def layerwise(cfg):
    """Hidden state after each layer: the program's layer function on the
    program's own previous state, beside the reference's."""
    seg, pos = jnp.zeros(T, jnp.int32), jnp.arange(T)
    mask = qwen2.segment_causal_mask(seg, None, None)
    x = params["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.dtype(cfg.dtype))
    xr = x.astype(jnp.float32)
    fn = jax.jit(lambda lp, x, i: qwen2.decoder_layer(lp, x, None, None, seg, mask, cfg, i)[0],
                 static_argnums=2)
    out = []
    for i in range(cfg.num_hidden_layers):
        lp = jamba_ref.layer_params(params, i)
        x = fn(lp, x, i)
        with jax.default_matmul_precision("highest"):
            xr = jamba_ref._layer(lp, xr, st=jamba_ref.layer_statics(cfg, i))
        d = float(jnp.linalg.norm(x.astype(jnp.float32) - xr) / jnp.linalg.norm(xr))
        out.append((i, cfg.layer_types[i], round(d, 5), round(float(jnp.sqrt(jnp.mean(xr * xr))), 3)))
    return out


def logprob_error(cfg):
    logits = jax.jit(lambda p: qwen2.forward(p, jnp.asarray(ids), jnp.arange(T),
                                             jnp.zeros(T, jnp.int32), cfg))(params)
    lp = jax.nn.log_softmax(logits[:-1], axis=-1)
    got = np.asarray(jnp.take_along_axis(lp, jnp.asarray(ids[1:])[:, None], axis=-1)[:, 0])
    # (random labels: the sampled tokens of a run sit higher; the same scale)
    d = np.abs(got - ref_lp)
    greedy = np.asarray(jnp.argmax(logits[:-1], -1))
    return {"mean_abs": float(d.mean()), "max_abs": float(d.max()),
            "p90": float(np.quantile(d, 0.9)), "greedy_first": greedy[:4].tolist()}


variants = os.environ.get("PROBE_VARIANTS", "bf16,f32act,uz32").split(",")
for v in variants:
    c = cfg
    orig = qwen2._ssm_project
    if v == "f32act":
        import dataclasses
        c = dataclasses.replace(cfg, dtype="float32")
    if v == "uz32":
        def project(layer_p, x, cfg_):
            uz = jnp.einsum("...h,hc->...c", x, layer_p["in_kernel"],
                            preferred_element_type=jnp.float32)
            return uz[..., : cfg_.ssm_inner], uz[..., cfg_.ssm_inner:]
        qwen2._ssm_project = project
    print("variant", v, json.dumps(logprob_error(c)), flush=True)
    if v in ("bf16", "uz32"):
        for row in layerwise(c):
            print("   layer", *row, flush=True)
    qwen2._ssm_project = orig
