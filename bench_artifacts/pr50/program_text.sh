#!/bin/bash
# The existing configurations' lowered programs, parent (`_parent/`: `git archive <parent> | tar -x -C
# _parent`) against this tree, on the CPU: prints both lists' diff; empty = text-equal.
set -e
export JAX_PLATFORMS=cpu
out=${1:-/tmp}
python bench_artifacts/pr50/program_text.py --root _parent > $out/programs_parent.txt 2>/dev/null
python bench_artifacts/pr50/program_text.py > $out/programs_change.txt 2>/dev/null
wc -l $out/programs_parent.txt $out/programs_change.txt
diff $out/programs_parent.txt $out/programs_change.txt && echo "TEXT-EQUAL: every program of every kind"
