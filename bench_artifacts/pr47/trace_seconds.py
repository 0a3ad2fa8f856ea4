"""Host seconds JAX spends tracing and lowering `jit_grad_step` (what a warm
set-up pays a micro-batch shape), for the tree on PYTHONPATH, on THIS host's
CPU for a described v5e: a host number, not a device metric.

    JAX_PLATFORMS=cpu PYTHONPATH=_parent python bench_artifacts/pr47/trace_seconds.py 0.5b 8192 1
    JAX_PLATFORMS=cpu PYTHONPATH=. python bench_artifacts/pr47/trace_seconds.py 0.5b 8192 1
"""

import json
import sys

from jax import monitoring

sys.path.insert(0, __file__.rsplit("/", 1)[0])
import aot_memory  # noqa: E402

ACC = {"trace": 0.0, "lower": 0.0}
EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}


def on_duration(event, secs, **_):
    if event in EVENTS:
        ACC[EVENTS[event]] += secs


if __name__ == "__main__":
    monitoring.register_event_duration_secs_listener(on_duration)
    aot_memory.main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), [int(sys.argv[4])])
    print(json.dumps({"sets": int(sys.argv[4]), **{k: round(v, 2) for k, v in ACC.items()}}))
