#!/usr/bin/env bash
# Pre-PR check: areal-lint (AR1xx concurrency, AR2xx JAX hot-path, AR3xx
# wire contracts) against the checked-in baseline, then a bytecode compile
# of the whole tree. The repo-wide run is what judges the AR3xx pairing
# contracts — it sees both the server and client side of every route,
# seam, and metrics key (partial sweeps skip a pairing direction whose
# reference set is absent, so they stay quiet rather than wrong).
#
#   tools/lint.sh            # gate: what CI / the tier-1 suite enforces
#   tools/lint.sh --all      # also sweep tools/ and tests/
#                            # (informational; tests/ has known AR201s in
#                            # oracle loops where sync cost is irrelevant,
#                            # and standalone AR301/AR302 noise from test
#                            # doubles that register no real routes/seams)
#   tools/lint.sh --changed [BASE]
#                            # fast pre-commit mode: lint + compile ONLY
#                            # the .py files changed vs BASE (default
#                            # main) — committed AND working-tree changes
#
# Run from the repo root. Exit 0 = clean.

set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--changed" ]]; then
    base="${2:-main}"
    # worktree-vs-base diff catches staged, unstaged AND committed changes;
    # --diff-filter=d drops deletions (nothing left to lint)
    changed=()
    while IFS= read -r f; do
        # seeded-bad fixtures are negative test data that fire by design;
        # the suite pins their findings, the pre-commit lint skips them
        [[ "$f" == tests/fixtures/lint/* ]] && continue
        [[ -f "$f" ]] && changed+=("$f")
    done < <(
        {
            git diff --name-only --diff-filter=d "$base" -- '*.py'
            # untracked new files are changes too — a brand-new module
            # must not skip its own pre-commit lint
            git ls-files --others --exclude-standard -- '*.py'
        } | sort -u
    )
    if [[ ${#changed[@]} -eq 0 ]]; then
        echo "lint --changed: no python files changed vs $base"
        echo "lint: OK"
        exit 0
    fi
    echo "== areal-lint --changed (${#changed[@]} file(s) vs $base) =="
    printf '  %s\n' "${changed[@]}"
    # in-process families judge each file on its own
    python -m areal_tpu.analysis "${changed[@]}" \
        --baseline tools/lint_baseline.json --rules AR1XX,AR2XX
    echo "== areal-lint --changed: AR3xx wire contracts (repo-wide) =="
    # pairing contracts (routes/seams/metrics/knobs) span files a diff
    # never isolates — a changed-files sweep would miss one side of every
    # pair, so the wire family always runs over the whole tree (it is
    # pure-AST and takes milliseconds)
    python -m areal_tpu.analysis areal_tpu/ \
        --baseline tools/lint_baseline.json --rules AR3XX
    echo "== compileall (changed files) =="
    python -m compileall -q "${changed[@]}"
    echo "lint: OK"
    exit 0
fi

echo "== areal-lint (areal_tpu/ vs tools/lint_baseline.json) =="
python -m areal_tpu.analysis areal_tpu/ --baseline tools/lint_baseline.json

if [[ "${1:-}" == "--all" ]]; then
    echo "== areal-lint sweep: tools/ (gating) =="
    python -m areal_tpu.analysis tools/*.py --no-baseline
    echo "== areal-lint sweep: tests/ (informational) =="
    python -m areal_tpu.analysis tests/ --no-baseline || true
fi

echo "== compileall =="
python -m compileall -q areal_tpu tests tools examples
echo "lint: OK"
