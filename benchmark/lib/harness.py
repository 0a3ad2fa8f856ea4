"""What every kind of cell shares: the experiment config from the cell's
files, seeded weights, the compile-cache watch, the profiler window, the
device line and the comparison with the float32 reference."""

from __future__ import annotations

import copy
import json
import os
import shutil
import time

import numpy as np

from . import spans as spans_lib
from . import xplane

# files of the configuration that are not part of the model's config.json
CONFIG_META_KEYS = ("source", "reduced", "assumed", "deployment", "parameters")


class Runtime:
    """One run: the parsed command line, the cell, the clock and the spans."""

    def __init__(self, args, cell: dict, registry, t_start: float):
        self.args = args
        self.cell = cell
        self.registry = registry
        self.t_start = t_start
        self.spans = spans_lib.Spans()
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        # run-time files: inside the checkout, at a fixed path, git-ignored
        self.workdir = os.path.join(registry.root, ".bench_work", cell["name"])
        os.makedirs(self.workdir, exist_ok=True)
        self.cache = CacheWatch()
        self.notes: dict = {}

    def note(self, **kv) -> None:
        """Goes on an earlier line of the output, never into the result."""
        self.notes.update(kv)
        print("note: " + json.dumps(kv, default=float), flush=True)


class CacheWatch:
    """Persistent-compile-cache requests, hits and misses as JAX reports
    them (copied from chip_smoke.py). A miss inside the window is a real
    compilation: the warm-up missed a shape and the window means nothing."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax.monitoring

        self.counts = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def model_dir(rt: Runtime) -> str:
    """The configuration's published keys as a `config.json` in a directory,
    which is how the program takes a model's geometry."""
    d = os.path.join(rt.workdir, "model")
    os.makedirs(d, exist_ok=True)
    hf = {k: v for k, v in rt.cell["config_file"].items() if k not in CONFIG_META_KEYS}
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf, f, indent=1, sort_keys=True)
    return d


def experiment_config(rt: Runtime):
    """The cell's `experiment` (a GRPOConfig as the recipe YAML would give
    it) with what follows from the traffic and the configuration filled in,
    loaded through the program's own loader."""
    from areal_tpu.api.cli_args import GRPOConfig, load_expr_config

    exp = copy.deepcopy(rt.cell["experiment"])
    traffic = rt.cell["traffic_file"]
    mdir = model_dir(rt)
    actor = exp.setdefault("actor", {})
    actor["path"] = mdir
    actor["init_from_scratch"] = True
    actor["group_size"] = traffic["n_samples"]
    exp.setdefault("decode", {})["model_path"] = mdir
    g = exp.setdefault("gconfig", {})
    g["n_samples"] = traffic["n_samples"]
    g["max_new_tokens"] = traffic["output_len"]["hi"]
    if "groups_per_batch" in traffic:
        exp["train_dataset"] = {"path": "benchmark-traffic",
                                "batch_size": traffic["groups_per_batch"]}
        exp.setdefault("rollout", {})["consumer_batch_size"] = traffic["groups_per_batch"]
    # the program's defaults are fixed paths under /tmp, which two checkouts
    # on one machine would share
    exp["cluster"] = {
        "fileroot": os.path.join(rt.workdir, "files"),
        "name_resolve": {"type": "nfs",
                         "nfs_record_root": os.path.join(rt.workdir, "name_resolve")},
    }
    path = os.path.join(rt.workdir, "experiment.yaml")
    import yaml

    with open(path, "w") as f:
        yaml.safe_dump(exp, f, sort_keys=False)
    config, _ = load_expr_config(["--config", path], GRPOConfig)
    return config


def reseed_actor(actor, seed: int) -> None:
    """Replace the trainer's parameters by the seeded tree, same dtype, same
    placement. The optimizer state (zeros) does not depend on them."""
    import jax

    from .weights import seeded_params

    shardings = jax.tree.map(lambda x: x.sharding, actor.params)
    actor.params = seeded_params(actor.model_config, seed, shardings)


def device_line(extra: dict | None = None) -> dict:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": max(peaks)}
    out.update(extra or {})
    return out


class TraceWindow:
    """The profiler around a short sub-window, and its reduction."""

    def __init__(self, rt: Runtime):
        self.rt = rt
        self.dir = os.path.join(rt.workdir, "trace")
        self._span = None
        self.host = (0.0, 0.0)

    def start(self) -> None:
        import jax.profiler

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = self.rt.spans.span("traced_window")
        self._span.__enter__()
        self._t0 = time.monotonic()

    def stop(self) -> None:
        import jax.profiler

        self._span.__exit__(None, None, None)
        self.host = (self._t0, time.monotonic())
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        """{"trace", "trace_window", "busy", "breakdown"}."""
        trace = xplane.load(xplane.find_xplane(self.dir))
        lo, hi = xplane.window(trace)
        return {
            "trace": trace,
            "trace_window": (lo, hi),
            "busy": xplane.busy(trace, lo, hi),
            "breakdown": {"device_ops": xplane.top_ops(trace, lo, hi, 10),
                          "idle_gaps": xplane.idle_gaps(trace, lo, hi, 5)},
        }


class StepTrace:
    """For the kinds that count steps: traces `trace_steps` whole steps from
    step `trace_from_step` (counted from the window's opening) and keeps
    their sequence lengths, which the roofline needs. Does nothing in an
    untraced run."""

    def __init__(self, rt: Runtime):
        self.window = TraceWindow(rt) if rt.trace else None
        self.first = int(rt.cell.get("trace_from_step", 2))
        self.last = self.first + int(rt.cell.get("trace_steps", 3)) - 1
        self.lengths: list[int] = []
        self.steps = 0
        self._on = False

    def before_step(self, i: int) -> None:
        if self.window and i == self.first:
            self.window.start()
            self._on = True

    def after_step(self, i: int, lengths: list[int]) -> None:
        if self._on:
            self.lengths += lengths
            self.steps += 1
            if i == self.last:
                self.close()

    def close(self) -> None:
        if self._on:
            self.window.stop()
            self._on = False

    def reduce(self) -> dict:
        """Empty if the window closed before the first traced step."""
        return self.window.reduce() if self.window and self.lengths else {}


def engine_counters(m0: dict, m1: dict, names, decode_config) -> dict:
    """Deltas of the decode engine's counters over the window, with the two
    engine settings a ratio needs."""
    out = {k: m1[k] - m0[k] for k in names}
    out["new_tokens_per_chunk"] = decode_config.new_tokens_per_chunk
    out["max_running_requests"] = decode_config.max_running_requests
    return out


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray) -> dict:
    """One sample: the program's log-probabilities against the reference's."""
    from ..reference.qwen2_ref import MAX_ABS_TOL, MEAN_ABS_TOL

    d = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    ok = bool(np.isfinite(d).all() and d.mean() <= MEAN_ABS_TOL and d.max() <= MAX_ABS_TOL)
    return {"what": name, "ok": ok, "tokens": int(d.size),
            "mean_abs": float(d.mean()), "max_abs": float(d.max())}


def finite_steps(step_stats: list) -> list[str]:
    """Every minibatch of every step: loss and grad-norm finite, grad-norm
    > 0. Returns what is wrong (empty when all is well)."""
    import math

    bad = []
    for i, minibatches in enumerate(step_stats):
        for mb in minibatches:
            loss = next((v for k, v in mb.items() if k.endswith("/loss") or k == "loss"), None)
            gn = next((v for k, v in mb.items() if k.endswith("grad_norm")), None)
            if loss is None or gn is None or not (
                    math.isfinite(loss) and math.isfinite(gn) and gn > 0):
                bad.append(f"step {i}: loss={loss} grad_norm={gn}")
    return bad
