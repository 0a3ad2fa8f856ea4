"""READ, not repair: a cell's own command (`benchmark/run.py` of `--root`) run
in this process with every program JAX compiles or reads from the persistent
cache NAMED and timed, so that a `compile_requests_in_window` that is not zero
can be put on a program. Nothing of the run is changed.

    python bench_artifacts/pr47/name_compiles.py --root _parent \
        --workload rollout-sdar-gsm8k --seed <n> --seconds 51 --trace 0

A line a program: `compiled: t=<s since the process began> <hit|MISS> <name>`;
the result line's `setup_s` (counted from `run.py`'s own start, printed here
as `run.py starts t=`) says where the window opened."""

import time

T0 = time.monotonic()

import logging  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import runpy  # noqa: E402
import sys  # noqa: E402


def main():
    argv = sys.argv[1:]
    root = os.getcwd()
    if "--root" in argv:
        i = argv.index("--root")
        root = os.path.abspath(argv[i + 1])
        del argv[i:i + 2]
    os.chdir(root)
    sys.path.insert(0, root)
    import jax
    from jax import monitoring

    jax.config.update("jax_log_compiles", True)
    last = {"miss": False}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            last["miss"] = True

    monitoring.register_event_listener(on_event)

    class Names(logging.Handler):
        def emit(self, record):
            m = re.match(r"Finished XLA compilation of (\S+) in ([\d.e-]+) sec", record.getMessage())
            if m:
                kind = "MISS" if last["miss"] else "hit"
                last["miss"] = False
                print(f"compiled: t={time.monotonic() - T0:.3f} {kind} {m.group(1)} "
                      f"({float(m.group(2)):.2f} s)", flush=True)

    for name in ("jax._src.dispatch", "jax._src.interpreters.pxla", "jax._src.compiler", "jax"):
        lg = logging.getLogger(name)
        lg.addHandler(Names())
    logging.getLogger("jax").setLevel(logging.WARNING)
    print(f"run.py starts t={time.monotonic() - T0:.3f}", flush=True)
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
