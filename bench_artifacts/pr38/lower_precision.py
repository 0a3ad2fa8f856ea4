"""The two must-fail readings of `rollout-dsv2-longctx` (ISSUE 38), one
precision below what the configuration states:

    python bench_artifacts/pr38/lower_precision.py weights --workload rollout-dsv2-longctx ...
        the reference with its weights at float8's 3 mantissa bits
    python bench_artifacts/pr38/lower_precision.py pool --workload rollout-dsv2-longctx ...
        the latent pool's rows rounded to float8 (e4m3) as they are written,
        by the prefill and by every decode step

The rest of the line is `benchmark/run.py`'s; the run is the benchmark's own,
with one function replaced before it starts. Either has to come out
`correct: false` by at least one of `deepseek_v2_ref.py`'s bounds."""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

what, argv = sys.argv[1], sys.argv[2:]
import benchmark.run as run  # noqa: E402

if what == "pool":
    import jax.numpy as jnp

    from areal_tpu.models import qwen2

    pad = qwen2._latent_pool_row
    qwen2._latent_pool_row = lambda row, lanes: pad(
        row.astype(jnp.float8_e4m3fn).astype(row.dtype), lanes)
elif what == "weights":
    from benchmark.reference import deepseek_v2_ref

    deepseek_v2_ref.token_logprobs = functools.partial(
        deepseek_v2_ref.token_logprobs, weight_bits=3)
else:
    raise SystemExit(f"what to lower: 'pool' or 'weights', not {what!r}")
code = run.main(argv)
sys.stdout.flush()
os._exit(code)
