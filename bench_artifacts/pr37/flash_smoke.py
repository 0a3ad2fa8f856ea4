"""`tools/tpu_smoke.py`'s flash cases alone (PR 37): `chiprun -- python bench_artifacts/pr37/flash_smoke.py`."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import tpu_smoke  # noqa: E402

bad = 0
for name, path, thunk in tpu_smoke.cases():
    if name.startswith("flash_attention"):
        ok, detail = thunk()
        bad += not ok
        print(f"{'OK  ' if ok else 'FAIL'} {name} [{path}]: {detail}", flush=True)
print("RESULT:", "PASS" if not bad else f"{bad} FAILURES")
raise SystemExit(1 if bad else 0)
