"""The latent decode kernel alone on the chip (ISSUE 38): `tools/tpu_smoke.py`'s
three new cases, then the kernel at the cell's shape: 64 slots, a table of 128
columns (context 16,384), ragged depths, two slots in three active, against
`jax.numpy` at float32.

    chiprun -- python bench_artifacts/pr38/kernel_alone.py
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import tpu_smoke  # noqa: E402

failed = 0
for name, _, thunk in tpu_smoke.cases():
    if "latent" in name or "of 160" in name:
        ok, detail = thunk()
        failed += not ok
        print(f"{'OK  ' if ok else 'FAIL'} {name}: {detail}", flush=True)
for mix in ("ragged", "deep"):
    ok, detail = tpu_smoke.latent_case(mix, R=64, nb=128, L=2, layer=1)
    failed += not ok
    print(f"{'OK  ' if ok else 'FAIL'} the cell's shape (64 slots x 128 columns), {mix}: {detail}",
          flush=True)
sys.exit(1 if failed else 0)
