"""chip_smoke.py's loop phase alone (no kernel phase): the colocated async
GRPO loop at Qwen2.5-0.5B's geometry, a trainer and a decode engine on one
chip. Prints what each engine declared of the chip (`hbm.declare_resident`)
beside the allocator's own reading, the set each new `jit_grad_step` keeps,
and every step's loss, grad norm and `remat_kept_sets`.

    chiprun -- python bench_artifacts/pr47/loop_only.py [key=value ...]
"""

import logging
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "examples")]


def main() -> int:
    import jax

    import gsm8k_grpo
    from areal_tpu.utils import hbm

    said: list[str] = []
    listener = logging.Handler()
    listener.emit = lambda r: said.append(f"{r.levelname} {r.getMessage()}")
    logging.getLogger().addHandler(listener)
    argv = ["--config", os.path.join(REPO, "examples/configs/qwen2.5_0.5b_grpo_smoke.yaml"),
            f"actor.path={os.path.join(REPO, 'examples/configs/qwen2.5-0.5b')}",
            f"cluster.fileroot={os.path.join(REPO, 'chiprun_out', 'pr47_loop')}"]
    argv += sys.argv[1:]

    def after_step(step, batch, actor, rollout):
        if step == 0:
            account = {type(o).__name__: n for o, n in hbm._DECLARED.items()}
            stats = jax.devices()[0].memory_stats() or {}
            print(f"declared a chip: {account} sum={sum(account.values())} "
                  f"allocator: in_use={stats.get('bytes_in_use')} "
                  f"peak={stats.get('peak_bytes_in_use')} limit={stats.get('bytes_limit')}",
                  flush=True)

    history = gsm8k_grpo.main(argv, after_step=after_step)
    ok = True
    for i, minibatches in enumerate(history):
        for mb in minibatches:
            loss, gnorm = mb["grpo_actor/loss"], mb["grpo_actor/grad_norm"]
            ok &= math.isfinite(loss) and math.isfinite(gnorm) and gnorm > 0
            print(f"step {i}: loss={loss:.5f} grad_norm={gnorm:.4f} "
                  f"imp_weight={mb['grpo_actor/behave_imp_weight']:.4f} "
                  + " ".join(f"{k.split('/')[-1]}={v}" for k, v in mb.items()
                             if "remat_kept" in k or k.endswith("/compiles")))
    for line in said:
        if "grad_step T=" in line or "not in the account" in line or "refused" in line:
            print(line)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"allocator at the end: peak={stats.get('peak_bytes_in_use')} "
          f"limit={stats.get('bytes_limit')}")
    print(f'{{"ok": {str(bool(ok)).lower()}, "steps": {len(history)}}}')
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
