"""What ends each hold: reads a `PR51_TIMELINE` file of `run_cell.py` and prints,
for every chunk dispatched, whether its dispatch was held, what ended the hold
(`deadline`: the wait ran to it; `admit`: the hold's admission was still
running at the deadline; `ended`: the wait saw the chunk in flight end, the
estimate overshot; `early`: every slot was filled or a request stayed queued),
how long the read-back of the chunk before then waited for the device (the lead
the dispatch really had) and, for the admissions that took 20 ms or more, which
of the programs enqueued since the last chunk was the first to wait.

    python bench_artifacts/pr51/timeline_table.py chiprun_out/pr51_<tag>_timeline.jsonl
"""

import collections
import json
import sys


def main() -> None:
    events = [json.loads(line) for line in open(sys.argv[1])]
    rows, holds, admits, slow = [], [], [], []
    waits = {e["chunk"]: e for e in events if e["what"] == "consume"}
    admissions = 0
    for e in events:
        if e["what"] == "hold":
            holds.append(e)
        elif e["what"] == "admit":
            n = (e.get("admissions") or 0) - admissions
            admissions = e.get("admissions") or 0
            admits.append((e, n))
        elif e["what"] in ("fork", "prefill"):
            slow.append(e)
        elif e["what"] == "dispatch" and e["chunk"] is not None:
            how = "not held"
            if holds:
                deadline = holds[-1]["deadline"]
                if holds[-1]["ended"]:
                    how = "ended"
                elif admits and admits[-1][0]["t1"] >= deadline > holds[0]["t0"]:
                    how = "admit"
                elif holds[-1]["t1"] >= deadline:
                    how = "deadline"
                else:
                    how = "early"
            before = waits.get(e["chunk"] - 1)
            first = min(slow, key=lambda s: s["t0"]) if slow else None
            rows.append({
                "chunk": e["chunk"], "how": how, "live": e["live"], "left queued": e["queued"],
                "held s": round(sum(h["t1"] - h["t0"] for h in holds), 3),
                "admitted": sum(n for _, n in admits),
                "admit s": round(sum(a["t1"] - a["t0"] for a, _ in admits), 3),
                "programs ahead": e.get("programs_ahead"),
                "first slow call": None if first is None else
                "%s #%d %.3f s" % (first["what"], first["nth"], first["t1"] - first["t0"]),
                "dispatch s": round(e["t1"] - e["t0"], 4),
                "read-back waited s": None if before is None else round(before["t1"] - before["t0"], 3),
                "seen to end": None if before is None else before.get("seen_to_end"),
            })
            holds, admits, slow = [], [], []
    keys = list(rows[0])
    print(" | ".join(keys))
    for r in rows:
        print(" | ".join(str(r[k]) for k in keys))
    print("\nwhat ended the holds:", dict(collections.Counter(r["how"] for r in rows)))
    for how in ("deadline", "admit", "ended", "early"):
        lead = [r["read-back waited s"] for r in rows
                if r["how"] == how and r["read-back waited s"] is not None]
        if lead:
            lead.sort()
            print("  %-8s read-back of the chunk before waited: median %.3f s, largest %.3f s" % (
                how, lead[len(lead) // 2], lead[-1]))
    nth = sorted(int(r["first slow call"].split("#")[1].split()[0]) for r in rows if r["first slow call"])
    if nth:
        print("  the first call of an admission to take 20 ms or more was program number (since the "
              "last chunk went out): smallest %d, median %d, largest %d, over %d admissions" % (
                  nth[0], nth[len(nth) // 2], nth[-1], len(nth)))
    quick = sorted(r["programs ahead"] for r in rows if not r["first slow call"] and r["programs ahead"])
    if quick:
        print("  admissions with no such call enqueued at most %d programs" % quick[-1])


if __name__ == "__main__":
    main()
