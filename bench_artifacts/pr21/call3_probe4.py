"""Four-chip host probe: can libtpu split one host's chips between processes?
Parent stays off JAX; children print their devices. Every child has a timeout."""
import os, subprocess, sys, time, json

CHILD = r'''
import os, sys, time
t0=time.time()
import jax, jax.numpy as jnp
try:
    d = jax.devices()
    x = jnp.ones((512,512), jnp.bfloat16)
    y = float((x@x).sum())
    print("CHILD_OK", os.environ.get("TAG"), [(v.id, v.coords) for v in d], y, f"{time.time()-t0:.1f}s", flush=True)
    time.sleep(float(os.environ.get("HOLD", "8")))   # hold the chips so the siblings overlap
except Exception as e:
    print("CHILD_ERR", os.environ.get("TAG"), type(e).__name__, str(e)[:1500], flush=True)
    sys.exit(1)
'''

def run_variant(name, envs, timeout=100):
    print(f"=== variant {name}", flush=True)
    procs = []
    for i, env in enumerate(envs):
        e = dict(os.environ); e.update(env); e["TAG"] = f"{name}/{i}:{env.get('TPU_VISIBLE_CHIPS') or env.get('TPU_VISIBLE_DEVICES')}"
        procs.append(subprocess.Popen([sys.executable, "-c", CHILD], env=e, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    deadline = time.time() + timeout
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill(); out, _ = p.communicate(); out = (out or "") + "\nCHILD_TIMEOUT"
        lines = [l for l in out.splitlines() if "CHILD_" in l or "rror" in l or "FATAL" in l or "Check failed" in l]
        print(f"  rc={p.returncode}", *lines[-6:], sep="\n    ", flush=True)

def env(chips, bounds, port, var="TPU_VISIBLE_CHIPS", controller=True):
    e = {var: ",".join(map(str, chips)), "TPU_PROCESS_BOUNDS": "1,1,1"}
    if bounds: e["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    if controller:
        e["TPU_MESH_CONTROLLER_ADDRESS"] = f"localhost:{port}"; e["TPU_MESH_CONTROLLER_PORT"] = str(port)
    return e

which = sys.argv[1:] or ["A", "B", "C", "D"]
if "A" in which:  # the launcher's plan for jax:d2t1+d2, y-pair bounds
    run_variant("A:1+1+2(1,2,1)", [env([0], "1,1,1", 8476), env([1], "1,1,1", 8477), env([2,3], "1,2,1", 8478)])
if "B" in which:  # same with x-pair bounds
    run_variant("B:1+1+2(2,1,1)", [env([0], "1,1,1", 8476), env([1], "1,1,1", 8477), env([2,3], "2,1,1", 8478)])
if "C" in which:  # only visibility, no bounds, no controller ports
    run_variant("C:visible-only", [{"TPU_VISIBLE_CHIPS": "0"}, {"TPU_VISIBLE_CHIPS": "1"}, {"TPU_VISIBLE_CHIPS": "2,3"}])
if "D" in which:  # no restriction at all: two processes fight for all four
    run_variant("D:unrestricted-pair", [{}, {}], timeout=80)
