"""A replay of the decode scheduler's RULES over the benchmark's own traffic:
no model, no chip, time counted in chunks. What ISSUE 48's table came from,
rebuilt, so that the next issue on slot occupancy (ROADMAP S3) starts from a
model that was checked against the chip (PERF.md section 6, PR 48).

    python bench_artifacts/pr48/sched_replay.py [--seeds 6] [--cell <name>] [--chunks <n>]

The rules (`engine/jax_decode.py:_scheduler_loop`, run-ahead 1). A pass is
admit -> dispatch chunk p -> read chunk p-1 back:

- admit: queued requests, FIFO, into free slots; a pass prefills distinct
  prompts until `max_prefill_tokens` is spent (the first always; a group's
  members admitted beside their primary fork it for nothing; a later member
  forks a registered donor, or, where a slot holds a recurrent state, is
  prefilled again);
- dispatch: every occupied slot whose dispatched chunks do not yet cover its
  `max_new_tokens` is live and is projected 128 tokens on;
- read back: a request whose tokens are all there completes and frees its
  slot; a group returns with its last member, and the closed loop
  (`benchmark/lib/kind_rollout.py:ClosedLoop`) submits its successor, which
  the scheduler sees one pass LATE: the pass that follows has admitted and
  dispatched before the client's coroutine ran (`late`), or at once (`won`,
  the late-arrival race won: what dispatching as late as the device allows
  would give).
- `handover` (ISSUE 48): with nothing free, a queued request takes a slot
  whose occupant's last chunk is dispatched and unread.

Traffic is `benchmark/lib/traffic.py`'s, the first cohort scaled as the
traffic file says; slots, prefill budget and groups in flight are the cell's
files'. A window is `--chunks` chunks (default: what the ledger's
`chunk_device_ms.rollout` and the prefills' share leave of 51 s), then the
flush, which returns what was dispatched. Printed a cell and variant: slot
fill (tokens returned over chunks x 128 x slots, `decode_slot_occupancy_pct`'s
definition) and live slots a chunk, means over the seeds.

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
          "rollout-kimilinear-mixedlen": 21}
# kinds whose slot holds a recurrent state: a late group member is prefilled again
STATE_KINDS = ("rollout_linear", "rollout_kda")
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
            budget=int(decode.get("max_prefill_tokens", 8192)), chunks=CHUNKS[w["name"]])
    return out


class Req:
    __slots__ = ("group", "want", "bucket", "projected", "read")

    def __init__(self, group: int, want: int, prompt_len: int):
        self.group, self.want = group, want
        self.bucket = max(-(-(prompt_len - 1) // 64) * 64, 64)
        self.projected = self.read = 0  # tokens dispatched, tokens read back


def replay(cell: dict, seed: int, chunks: int, handover: bool, won: bool) -> tuple[float, float]:
    """(slot fill, live slots a chunk) of one window."""
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
    done_tokens = live_sum = 0
    for _ in range(chunks):
        if not won:
            late, arriving = arriving, 0  # seen a pass late
        # -- admit
        budget, prefilled, wave = cell["budget"], False, set()
        while queue:
            r = queue[0]
            free = [i for i, s in enumerate(table) if s is None]
            if not free and handover:
                free = [i for i, s in enumerate(table) if s.projected >= s.want and s in unread]
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
        if won:
            submit(returned)
        else:
            submit(late)
            arriving = returned
    # the flush returns what was dispatched, whole or not
    partial = sum(min(s.projected, s.want) for s in table if s is not None)
    partial += sum(s.want for s in unread if s not in table)  # handed over, unread
    return ((done_tokens + partial) / (chunks * CHUNK * slots), live_sum / chunks)


VARIANTS = (("as built", False, False), ("handover", True, False),
            ("race won", False, True), ("handover + race won", True, True))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--cell")
    ap.add_argument("--chunks", type=int, help="chunks in a window, in place of the table's")
    args = ap.parse_args()
    print(f"{'cell':30s} {'slots':>5s} {'chunks':>6s}  " + "  ".join(
        f"{name:>22s}" for name, _, _ in VARIANTS) + "   (slot fill %, live slots a chunk)")
    for name, cell in cells().items():
        if args.cell and name != args.cell:
            continue
        chunks = args.chunks or cell["chunks"]
        cols = []
        for _, handover, won in VARIANTS:
            runs = [replay(cell, 1000 + s, chunks, handover, won) for s in range(args.seeds)]
            fill = 100.0 * sum(r[0] for r in runs) / len(runs)
            live = sum(r[1] for r in runs) / len(runs)
            cols.append(f"{fill:13.1f} {live:8.1f}")
        print(f"{name:30s} {cell['slots']:5d} {chunks:6d}  " + "  ".join(cols))


if __name__ == "__main__":
    main()
