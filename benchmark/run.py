"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. Without a TPU, or with another
number of chips than the cell asks for, it exits non-zero and prints no
result line. Otherwise it builds the configuration's weights on the device
from `--seed`, draws the traffic from `--seed`, warms the cell's own shapes,
checks the program against the float32 reference outside the window, measures
for `--seconds`, and prints one JSON object as the last line of stdout
(`--trace 0`: the cell's end-to-end metrics; `--trace 1`: its per-layer
metrics, the device's busy seconds and the breakdown). `setup_s` runs from
the start of the process to the opening of the window.

The compile cache is where the program puts it
(`areal_tpu/platforms/__init__.py`: `JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`); run-time files go to `<checkout>/.bench_work/`.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a hang is a failure too: dump every thread's stack and leave
WATCHDOG_S = 1100


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv) -> int:
    args = parse(argv)
    from benchmark.lib.registry import Registry

    registry = Registry(ROOT)
    if args.seconds is None:
        args.seconds = float(registry.bench["run_seconds"])
    cell = registry.cell(args.workload)

    import areal_tpu  # noqa: F401 — the system under test must be here
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if devs[0].platform != "tpu" or len(devs) != int(cell["chips"]):
        print(f"benchmark: cell {cell['name']!r} needs {cell['chips']} TPU chip(s); "
              f"JAX reports {found}. No result.", file=sys.stderr)
        return 2
    from benchmark.lib import flops, harness, readers

    flops.peaks(found["kind"])  # an unknown device is an error, not a default
    # Where the cache lives is the program's business (see above). What goes
    # into it is the contract's: after a checkout's first run every program is
    # found there. JAX by default keeps only what took a second to compile, so
    # the short programs would compile anew in every run's set-up.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rt = harness.Runtime(args, cell, registry, T_START)
    print(f"benchmark: cell={cell['name']} kind={cell['kind']} seed={rt.seed} "
          f"seconds={rt.seconds} trace={int(rt.trace)} device={found}", flush=True)
    kind = importlib.import_module(f"benchmark.lib.kind_{cell['kind']}")
    result = kind.run(rt)

    ctx = result.pop("ctx")
    ctx.update(spans=rt.spans, device_kind=found["kind"], chips=int(cell["chips"]))
    e2e = result.pop("end_to_end")
    device_extra = {}
    if rt.trace:
        values = {}
        for m in registry.metrics("per_layer", cell["name"]):
            v = readers.read(registry.layer_metric(m["name"]), ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        if "busy" not in ctx:
            raise RuntimeError("the window closed before the traced sub-window opened: "
                               "nothing was traced (a longer --seconds is needed)")
        busy = ctx["busy"]
        device_extra = {"busy_s": busy["busy_s"], "window_s": busy["window_s"]}
        print("note: " + json.dumps({"end_to_end_in_traced_run": e2e}), flush=True)
    else:
        units = {m["name"]: m["unit"] for m in registry.metrics("end_to_end", cell["name"])}
        values = {k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units}
    if result.get("why_not"):
        print("note: " + json.dumps({"not_correct_because": result["why_not"]}), flush=True)
    line = {"correct": bool(result["correct"]), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": values,
            "device": harness.device_line(device_extra)}
    if rt.trace:
        line["breakdown"] = ctx["breakdown"]
    print(f"note: compile cache over the run: {rt.cache.snapshot()}", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        code = main(sys.argv[1:])
    except BaseException:  # noqa: BLE001 — report, then leave at once
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine threads must not keep a finished (or failed) run alive
    os._exit(code)
