"""Four-chip host probe 2: which chip pairs one process can own, and with
which TPU_CHIPS_PER_PROCESS_BOUNDS. Parent stays off JAX."""
import glob, os, subprocess, sys, time

CHILD = r'''
import os, sys, time
t0=time.time()
import jax, jax.numpy as jnp
try:
    d = jax.devices()
    x = jnp.ones((512,512), jnp.bfloat16)
    y = float((x@x).sum())
    print("CHILD_OK", os.environ.get("TAG"), [(v.id, v.coords) for v in d], f"{time.time()-t0:.1f}s", flush=True)
    time.sleep(float(os.environ.get("HOLD", "6")))
except Exception as e:
    print("CHILD_ERR", os.environ.get("TAG"), type(e).__name__, str(e)[:600], flush=True)
    sys.exit(1)
'''

def env(chips, bounds, port):
    return {"TPU_VISIBLE_CHIPS": ",".join(map(str, chips)), "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}", "TPU_MESH_CONTROLLER_PORT": str(port)}

def run_variant(name, envs, timeout=90):
    print(f"=== {name}", flush=True)
    procs = []
    for i, e_ in enumerate(envs):
        e = dict(os.environ); e.update(e_)
        e["TAG"] = f"chips={e_.get('TPU_VISIBLE_CHIPS')} bounds={e_.get('TPU_CHIPS_PER_PROCESS_BOUNDS')}"
        procs.append((e["TAG"], subprocess.Popen([sys.executable, "-c", CHILD], env=e, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)))
    deadline = time.time() + timeout
    for tag, p in procs:
        try:
            out, _ = p.communicate(timeout=max(1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill(); out, _ = p.communicate(); out = (out or "") + "\nCHILD_TIMEOUT"
        lines = [l[:400] for l in out.splitlines() if "CHILD_" in l]
        print(f"  [{tag}] rc={p.returncode} pid={p.pid}", *lines[-3:], sep="\n    ", flush=True)
        if p.returncode != 0:
            # libtpu's own log for that pid says why
            for f in glob.glob(f"/tmp/tpu_logs/*{p.pid}*"):
                tail = open(f, errors="replace").read().splitlines()
                keep = [l[:300] for l in tail if any(w in l for w in ("rror", "ERROR", "FATAL", "Check", "ound", "topology", "Topology"))]
                print(f"    libtpu log {f}:", *keep[-8:], sep="\n      ", flush=True)

print("dev nodes:", sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/*")))
run_variant("unrestricted, one process", [{}])
run_variant("rows as x-pairs (2,1,1)", [env([0, 1], "2,1,1", 8476), env([2, 3], "2,1,1", 8477)])
run_variant("same pairs as y-pairs (1,2,1)", [env([0, 1], "1,2,1", 8476), env([2, 3], "1,2,1", 8477)])
run_variant("columns (0,2),(1,3) as y-pairs (1,2,1)", [env([0, 2], "1,2,1", 8476), env([1, 3], "1,2,1", 8477)])
run_variant("the launcher's plan: 0 | 1 | 2,3 (2,1,1)", [env([0], "1,1,1", 8476), env([1], "1,1,1", 8477), env([2, 3], "2,1,1", 8478)])
