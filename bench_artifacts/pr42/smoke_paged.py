"""`tools/tpu_smoke.py`'s paged-attention cases alone (every head shape, the
ragged walks, the named groups, the ring), each through Mosaic on the chip.

    chiprun -- python bench_artifacts/pr42/smoke_paged.py
"""

import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from tools import tpu_smoke  # noqa: E402

bad = 0
for name, path, thunk in tpu_smoke.cases():
    if "paged_attention" not in name or "latent" in name:
        continue
    try:
        ok, detail = thunk()
    except Exception as e:  # noqa: BLE001 — the verdict is the error
        ok, detail = False, f"refused {type(e).__name__}: {e}"[:600]
        traceback.print_exc()
    bad += not ok
    print(f"{'OK  ' if ok else 'FAIL'} {name} [{path}]: {detail}", flush=True)
print("RESULT:", "PASS" if not bad else f"{bad} FAILURES")
sys.exit(1 if bad else 0)
