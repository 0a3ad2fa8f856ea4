"""The must-fail readings of `rollout-jamba2-reasoning` (ISSUE 50), one
precision below what the configuration states:

    python bench_artifacts/pr50/lower_precision.py state --workload rollout-jamba2-reasoning ...
        the recurrent state rounded to bf16 after every decode step of
        `ops/ssm_step.py` (in the chunk, and in `check_state`'s replay)
    python bench_artifacts/pr50/lower_precision.py weights --workload rollout-jamba2-reasoning ...
        the reference with its weights at float8's 3 mantissa bits

The rest of the line is `benchmark/run.py`'s; the run is the benchmark's own,
with one function replaced before it starts. `state` is held to the state's
own two bounds (`kind_rollout_ssm.py:check_state`), `weights` to
`jamba_ref.py`'s log-probability limits."""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

what, argv = sys.argv[1], sys.argv[2:]
import benchmark.run as run  # noqa: E402

if what == "weights":
    from benchmark.reference import jamba_ref

    jamba_ref.token_logprobs = functools.partial(jamba_ref.token_logprobs, weight_bits=3)
elif what == "state":
    import jax

    from areal_tpu.ops import ssm_step as op

    step = op.ssm_step

    def rounded(S, *a, **kw):
        y, S = step(S, *a, **kw)
        return y, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)

    op.ssm_step = rounded
else:
    raise SystemExit(f"what to lower: 'state' or 'weights', not {what!r}")
code = run.main(argv)
sys.stdout.flush()
os._exit(code)
