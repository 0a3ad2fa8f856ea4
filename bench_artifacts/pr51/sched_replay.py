"""A replay of the decode scheduler's RULES over the benchmark's own traffic:
no model, no chip, time counted in chunks. PR 48's replay (PERF.md section 6,
PR 48: checked against the chip there) with what ISSUE 51 adds: the Jamba2
cell (18 chunks; `rollout_ssm` among the kinds whose slot holds a state), and
a variant in which the dispatch is HELD and an arrival is seen only if it
comes before `deadline - lead`, so that the lead chosen is priced.

    python bench_artifacts/pr51/sched_replay.py [--seeds 6] [--cell <name>] [--chunks <n>]
        [--arrive-ms 10,500,1100,1500,2500]

The rules (`engine/jax_decode.py:_pass_locked`, run-ahead 1). A pass is
admit -> dispatch chunk p -> read chunk p-1 back:

- admit: queued requests, FIFO, into free slots; the passes before one
  dispatched chunk prefill distinct prompts until `max_prefill_tokens` is spent
  (the first always; a group's members admitted beside their primary fork it
  for nothing; a later member forks a registered donor, or, where a slot holds
  a recurrent state, is prefilled again);
- dispatch: every occupied slot whose dispatched chunks do not yet cover its
  `max_new_tokens` is live and is projected 128 tokens on;
- read back: a request whose tokens are all there completes and frees its
  slot; a group returns with its last member, and the closed loop
  (`benchmark/lib/kind_rollout.py:ClosedLoop`) submits its successor, which
  the scheduler sees one pass LATE as it was built: the pass that follows has
  admitted and dispatched before the client's coroutine ran (`late`), or at
  once (`won`: the dispatch waits for it, whatever time it takes);
- `handover` (ISSUE 48): with nothing free, a queued request takes a slot
  whose occupant's last chunk is dispatched and unread;
- `held` (ISSUE 51, what the engine does now): the successor comes
  `--arrive-ms` after the read-back (the client's turn-around: a coroutine's
  wake-up in the benchmark, a reward and a new prompt in a trainer's loop),
  and is admitted into the chunk whose hold it falls into: the one dispatched
  `chunk_ms - lead_ms` after the read-back it follows, if it comes before
  that, else the chunk after, as `late` (an arrival a whole chunk or more
  behind falls into a later hold by the same rule). `lead_ms` is the engine's:
  `_HOLD_MARGIN` (5%) of the chunk and the dispatch's own host time
  (`DISPATCH_MS`, measured on the chip: PERF.md section 6, PR 51). The hold is
  taken only where the engine takes it (nothing queued after admission and a
  slot empty or spent); where it is not, the chunk went out at the read-back
  and the arrival is `late`.

Traffic is `benchmark/lib/traffic.py`'s, the first cohort scaled as the
traffic file says; slots, prefill budget and groups in flight are the cell's
files'. A window is `--chunks` chunks (default: what the ledger's
`chunk_device_ms.rollout` and the prefills' share leave of 51 s), then the
flush, which returns what was dispatched. Printed a cell and variant: slot
fill (tokens returned over chunks x 128 x slots, `decode_slot_occupancy_pct`'s
definition) and live slots a chunk, means over the seeds; then the chip's
untraced fill beside it (PERF.md section 5's slot table).

Not modelled: the time a prefill takes (only the chunks a window holds), pool
pressure, the warm-up. `rollout-sdar-gsm8k`'s chunk is 32 blocks of 4 and never
hands a slot over; its rows are the as-built rules at 128 positions a chunk."""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import deque

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib.traffic import Traffic  # noqa: E402

CHUNK = 128
# chunks a 51 s window holds: 51 s x the chunk programs' share of the device
# over `chunk_device_ms.rollout` (ledger, PR 47's lines; PERF.md section 5)
CHUNKS = {"rollout-1.5b-gsm8k": 43, "rollout-olmoe-gsm8k": 31, "rollout-kexaone-mixedlen": 28,
          "rollout-qwen3next-mixedlen": 40, "rollout-sdar-gsm8k": 14, "rollout-dsv2-longctx": 23,
          "rollout-kimilinear-mixedlen": 21, "rollout-jamba2-reasoning": 18}
# `chunk_device_ms.rollout` (ledger, PR 50's lines; Qwen3-Next, OLMoE: PR 48's traced pairs)
CHUNK_MS = {"rollout-1.5b-gsm8k": 1131, "rollout-olmoe-gsm8k": 1775, "rollout-kexaone-mixedlen": 1509,
            "rollout-qwen3next-mixedlen": 1125, "rollout-sdar-gsm8k": 3787, "rollout-dsv2-longctx": 1141,
            "rollout-kimilinear-mixedlen": 1978, "rollout-jamba2-reasoning": 2876}
MARGIN = 0.05  # `engine/jax_decode.py:_HOLD_MARGIN`
DISPATCH_MS = 6.0  # the median of the last eight `_dispatch_chunk` calls: 5.9-6.0 ms (my chip runs e1-e3, PR 51)
# kinds whose slot holds a recurrent state: a late group member is prefilled again
STATE_KINDS = ("rollout_linear", "rollout_kda", "rollout_ssm")
# kinds whose projection is an upper bound: never handed over
INEXACT_KINDS = ("rollout_diffusion",)


def cells() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        if not cell["kind"].startswith("rollout"):
            continue
        with open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        decode = cell["experiment"]["decode"]
        out[w["name"]] = dict(
            kind=cell["kind"], traffic=traffic, slots=int(decode["max_running_requests"]),
            budget=int(decode.get("max_prefill_tokens", 8192)), chunks=CHUNKS[w["name"]],
            chunk_ms=CHUNK_MS[w["name"]])
    return out


class Req:
    __slots__ = ("group", "want", "bucket", "projected", "read")

    def __init__(self, group: int, want: int, prompt_len: int):
        self.group, self.want = group, want
        self.bucket = max(-(-(prompt_len - 1) // 64) * 64, 64)
        self.projected = self.read = 0  # tokens dispatched, tokens read back


def replay(cell: dict, seed: int, chunks: int, handover: bool, won: bool,
           arrive_ms: float | None = None) -> tuple[float, float, float]:
    """(slot fill, live slots a chunk, share of the chunks held) of one
    window. `arrive_ms`: the `held` rules, a successor that long after the
    read-back it follows; none: `late` or `won`."""
    tfile = cell["traffic"]
    traffic = Traffic(tfile, 1000, seed)
    inflight, slots = int(tfile["inflight_groups"]), cell["slots"]
    scales = dict(enumerate(traffic.cohort_scales(inflight)))
    queue: deque = deque()
    left: dict[int, int] = {}  # group -> members not yet returned
    next_group = 0

    def submit(n: int) -> None:
        nonlocal next_group
        for _ in range(n):
            g = traffic.group(next_group, scale=scales.get(next_group, 1.0))
            left[g.index] = len(g.output_lens)
            queue.extend(Req(g.index, n_out, len(g.prompt)) for n_out in g.output_lens)
            next_group += 1

    submit(inflight)
    table: list = [None] * slots
    unread: list = []  # the live requests of the chunk in flight
    arriving = 0  # groups whose predecessor returned in the last pass's read-back
    donors: set = set()  # groups whose prompt a slot has held (a fork is free)
    handover &= cell["kind"] not in INEXACT_KINDS
    state = cell["kind"] in STATE_KINDS
    done_tokens = live_sum = held_chunks = 0
    chunk_ms = cell["chunk_ms"]
    before_ms = chunk_ms - (MARGIN * chunk_ms + DISPATCH_MS)  # a held chunk goes out then
    falls: dict[int, list] = {}  # pass -> how far into its hold each arriving group comes

    def spent(s) -> bool:
        return s.projected >= s.want and s in unread

    def holds() -> bool:
        # `_hold_dispatch`: a chunk in flight, nothing left queued, a slot an
        # arrival could take, a slot that is live
        return bool(unread) and not queue and any(
            s is None or (handover and spent(s)) for s in table) and any(
            s is not None and s.projected < s.want for s in table)

    for p in range(chunks):
        late = 0
        if arrive_ms is None and not won:
            late, arriving = arriving, 0  # seen a pass late
        # -- admit: one budget a dispatched chunk
        budget, prefilled, wave = cell["budget"], False, set()

        def admit() -> None:
            nonlocal budget, prefilled
            while queue:
                r = queue[0]
                free = [i for i, s in enumerate(table) if s is None]
                if not free and handover:
                    free = [i for i, s in enumerate(table) if spent(s)]
                if not free:
                    break
                forks = r.group in wave or (r.group in donors and not state)
                if not forks:
                    if prefilled and r.bucket > budget:
                        break
                    budget, prefilled = budget - r.bucket, True
                    wave.add(r.group)
                    donors.add(r.group)
                table[free[0]] = queue.popleft()

        admit()
        held = arrive_ms is not None and holds()
        held_chunks += held
        for into in sorted(falls.pop(p, ())):
            if holds() and into < before_ms:
                submit(1)
                admit()
            else:
                late += 1  # the chunk has gone out: seen by the next pass
        # -- dispatch
        live = [s for s in table if s is not None and s.projected < s.want]
        for s in live:
            s.projected += CHUNK
        live_sum += len(live)
        # -- read the chunk before back
        returned = 0
        for s in unread:
            s.read += CHUNK
            if s.read >= s.want:
                done_tokens += s.want
                if s in table:
                    table[table.index(s)] = None
                left[s.group] -= 1
                returned += left[s.group] == 0
        unread = live
        if arrive_ms is not None:
            submit(late)
            behind, into = divmod(arrive_ms, chunk_ms)
            falls.setdefault(p + 1 + int(behind), []).extend([into] * returned)
        elif won:
            submit(returned)
        else:
            submit(late)
            arriving = returned
    # the flush returns what was dispatched, whole or not
    partial = sum(min(s.projected, s.want) for s in table if s is not None)
    partial += sum(s.want for s in unread if s not in table)  # handed over, unread
    return ((done_tokens + partial) / (chunks * CHUNK * slots), live_sum / chunks,
            held_chunks / chunks)


# (name, handover, race won): what the engine did before this PR, and the bound
VARIANTS = (("handover", True, False), ("handover + race won", True, True))

# the chip's UNTRACED fill (`decode_slot_occupancy_pct.rollout`'s ratio over the whole
# window) / live slots a chunk: PERF.md section 5's slot table. Before: the parent's runs of
# this PR's calls f1-f3 (PR 48's h2, h3 where the cell was not run); after: the change's
# (my chip runs, PR 51)
CHIP = {
    "rollout-1.5b-gsm8k": ("59.3-59.7 / 91.7-92.0", "65.8-66.4 / 101.4-102.5"),
    "rollout-olmoe-gsm8k": ("81.4 / 64.0", "81.4 / 64.0"),
    "rollout-kexaone-mixedlen": ("53.4 / 39.4", "55.1 / 40.7"),
    "rollout-qwen3next-mixedlen": ("86.4-86.7 / 64.0", "not run"),
    "rollout-sdar-gsm8k": ("56.4 / 91.1", "57.6 / 92.8"),
    "rollout-dsv2-longctx": ("29.1 / 23.2", "not run"),
    "rollout-kimilinear-mixedlen": ("63.6-63.7 / 94.1-94.3", "66.2-66.5 / 98.0-98.3"),
    # (never held on the chip: a request is always queued there, which this replay's
    # instantaneous admission does not reproduce)
    "rollout-jamba2-reasoning": ("81.1-81.6 / 225.9-227.1", "80.5-80.9 / 224.7-225.4"),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--cell")
    ap.add_argument("--chunks", type=int, help="chunks in a window, in place of the table's")
    ap.add_argument("--arrive-ms", default="10,500,1100,1500,2500",
                    help="the successor's turn-around after the read-back, one `held` column each")
    args = ap.parse_args()
    arrive = [float(a) for a in args.arrive_ms.split(",") if a]
    print("slot fill %, live slots a chunk; the `held` columns: and the share of the chunks held")
    print(f"{'cell':28s} {'slots':>5s} {'chunks':>6s} {'lead ms':>7s}  " + "  ".join(
        [f"{name:>19s}" for name, _, _ in VARIANTS]
        + [f"{'held, +%d ms' % a:>19s}" for a in arrive]) + "  | chip, untraced: before; after")
    for name, cell in cells().items():
        if args.cell and name != args.cell:
            continue
        chunks = args.chunks or cell["chunks"]
        cols = []
        for _, handover, won in VARIANTS:
            runs = [replay(cell, 1000 + s, chunks, handover, won) for s in range(args.seeds)]
            cols.append("%10.1f %8.1f" % (100.0 * sum(r[0] for r in runs) / len(runs),
                                          sum(r[1] for r in runs) / len(runs)))
        for a in arrive:
            runs = [replay(cell, 1000 + s, chunks, True, False, a) for s in range(args.seeds)]
            cols.append("%6.1f %6.1f %5.2f" % (100.0 * sum(r[0] for r in runs) / len(runs),
                                               sum(r[1] for r in runs) / len(runs),
                                               sum(r[2] for r in runs) / len(runs)))
        lead = MARGIN * cell["chunk_ms"] + DISPATCH_MS
        print(f"{name:28s} {cell['slots']:5d} {chunks:6d} {lead:7.0f}  " + "  ".join(cols)
              + "  | " + "; ".join(CHIP[name]))


if __name__ == "__main__":
    main()
