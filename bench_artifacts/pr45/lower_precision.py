"""The three must-fail readings of `rollout-kimilinear-mixedlen` (ISSUE 45),
one precision below what the configuration states:

    python bench_artifacts/pr45/lower_precision.py weights --workload rollout-kimilinear-mixedlen ...
        the reference with its weights at float8's 3 mantissa bits
    python bench_artifacts/pr45/lower_precision.py pool --workload rollout-kimilinear-mixedlen ...
        the latent pool's rows rounded to float8 (e4m3) as they are written,
        by the prefill and by every decode step
    python bench_artifacts/pr45/lower_precision.py state --workload rollout-kimilinear-mixedlen ...
        the recurrent state rounded to bf16 after every decode step of
        `ops/gdn_step.py` (in the chunk, and in `check_state`'s replay)

The rest of the line is `benchmark/run.py`'s; the run is the benchmark's own,
with one function replaced before it starts. `weights` and `pool` are held
to `kimi_linear_ref.py`'s log-probability bounds, `state` to the state's own
two bounds (`kind_rollout_kda.py:check_state`)."""

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
    from benchmark.reference import kimi_linear_ref

    kimi_linear_ref.token_logprobs = functools.partial(
        kimi_linear_ref.token_logprobs, weight_bits=3)
elif what == "state":
    import jax

    from areal_tpu.ops import gdn_step as op

    step = op.gdn_step

    def rounded(S, *a, **kw):
        o, S = step(S, *a, **kw)
        return o, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)

    op.gdn_step = rounded
else:
    raise SystemExit(f"what to lower: 'pool', 'weights' or 'state', not {what!r}")
code = run.main(argv)
sys.stdout.flush()
os._exit(code)
