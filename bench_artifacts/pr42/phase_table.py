"""The warm set-up by phase as a Markdown table, from `phase_clock.py`'s logs:
a column a group of logs, a row a phase, the median over the group's runs of
the seconds the phase took (JAX's own trace + lower seconds inside it in
brackets where there are any).

    python bench_artifacts/pr42/phase_table.py parent='chiprun_out/pr42_c1_*_parent_4*' \
        change='chiprun_out/pr42_c1_*_change_4*'
"""

import glob
import re
import statistics
import sys

LINE = re.compile(r"phase: (.*?) at=([\d.]+) took=([\d.]+) \[trace=([\d.]+) lower=([\d.]+)")
SETUP = re.compile(r'"setup_s": \{"value": ([\d.]+)')


def read(path):
    rows, setup = {}, None
    for line in open(path):
        m = LINE.match(line)
        if m:
            name = re.sub(r" \(a prefill wave.*", "", m.group(1))
            rows[name] = (float(m.group(3)), float(m.group(4)) + float(m.group(5)), float(m.group(2)))
        m = SETUP.search(line)
        if m:
            setup = float(m.group(1))
    return rows, setup


def main():
    groups = {}
    for arg in sys.argv[1:]:
        tag, pattern = arg.split("=", 1)
        groups[tag] = [read(p) for p in sorted(glob.glob(pattern))]
    med = statistics.median
    names = list(next(iter(groups.values()))[0][0])
    names = names[: names.index("the window opens") + 1]
    print("| phase | " + " | ".join(f"{t} ({len(g)} runs)" for t, g in groups.items()) + " |")
    print("| --- |" + " --- |" * len(groups))
    for name in names:
        cells = []
        for runs in groups.values():
            took = med(r[0][name][0] for r in runs)
            jaxs = med(r[0][name][1] for r in runs)
            cells.append(f"{took:.2f}" + (f" [{jaxs:.2f}]" if jaxs >= 0.005 else ""))
        if any(c != "0.00" for c in cells):
            print(f"| {name} | " + " | ".join(cells) + " |")
    print("| **the window opens at** | " + " | ".join(
        f"{med(r[0]['the window opens'][2] for r in runs):.2f}" for runs in groups.values()) + " |")
    print("| **`setup_s`** (each run) | " + " | ".join(
        ", ".join(f"{r[1]:.2f}" for r in runs) for runs in groups.values()) + " |")


if __name__ == "__main__":
    main()
