"""Every prefill bucket of `kimi-linear-48b-a3b` compiled for a DESCRIBED v5e
(no chip: `JAX_PLATFORMS=cpu`, libtpu's compiler alone), at the published
widths and the first four layers (KDA dense, KDA, KDA, MLA: two sparse layers
behind KDA mixers; the full depth fails where these fail), one prompt a
program as the engine builds it above 1,024 tokens.

    JAX_PLATFORMS=cpu python bench_artifacts/pr45/prefill_buckets.py [<tokens> ...]

With no argument: every bucket of 64 to 8,192 tokens, five at a time (8 min on
8 cores). Prints `<tokens> ok` or the compiler's refusal. PR 45 found ONE
refusal, 1,536 tokens (12,288 pair rows of 2,304 lanes: a fusion XLA:TPU makes
around `mlp/dispatch`'s row gather runs out of scoped VMEM). The model's code
does not step around it: the engine runs a refused bucket as a pass a bucket
wider (`engine/jax_decode.py:_PrefillOrWider`), whatever the shape."""

import json
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
LAYERS = 4


def compile_bucket(T: int) -> str:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import qwen2

    with open(os.path.join(ROOT, "benchmark/configs/kimi-linear-48b-a3b.json")) as f:
        hf = dict(json.load(f), num_hidden_layers=LAYERS)
    cfg = qwen2.ModelConfig.from_hf_config(hf, dtype="bfloat16", param_dtype="bfloat16")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.PRNGKey(0)))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), p)

    def prefill_batched(p, ids_b, lens_b):
        def core(ids, true_len):
            return qwen2.prefill(p, ids, jnp.arange(T), cfg, valid=jnp.arange(T) < true_len,
                                 with_logits=False)
        return jax.vmap(core)(ids_b, lens_b)

    args = (p, jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one))
    try:
        jax.jit(prefill_batched).trace(*args).lower(lowering_platforms=("tpu",)).compile()
        return "ok"
    except Exception as e:  # noqa: BLE001 — whatever the compiler refuses with
        return "REFUSED " + str(e)[:240].replace("\n", " ")


if __name__ == "__main__":
    if len(sys.argv) == 2:
        print(sys.argv[1], compile_bucket(int(sys.argv[1])), flush=True)
        sys.exit(0)
    buckets = [int(a) for a in sys.argv[1:]] or list(range(64, 8192 + 1, 64))
    # a process a bucket (a compile keeps its memory), five at a time; libtpu's
    # lock lets one process in at a time unless told otherwise
    env = dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    running = []
    for T in buckets:
        running.append(subprocess.Popen([sys.executable, __file__, str(T)], env=env,
                                        stderr=subprocess.DEVNULL))
        if len(running) == 5:
            running.pop(0).wait()
    for proc in running:
        proc.wait()
