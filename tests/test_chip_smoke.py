"""What the chip gate and its supports promise, as far as a CPU can check.

`chip_smoke.py` itself only passes on a TPU (through the chip tool); here we
pin the other half of its contract — that nothing lets a run without the
chip pass for one with it: the smoke refuses a CPU, the compile cache goes
where the environment says, no utilization is reported against an invented
peak, the launcher gives every child its own chips, and a Pallas kernel that
cannot tile a shape says so instead of giving way to XLA.
"""

import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    assert '"ok"' not in r.stdout  # no result line


@pytest.fixture()
def config_updates(monkeypatch):
    """Record `jax.config.update` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_follows_the_environment(monkeypatch, config_updates):
    from areal_tpu.platforms import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    enable_compilation_cache()
    assert config_updates == []  # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch, config_updates):
    from areal_tpu.platforms import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compilation_cache()
    assert config_updates == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))
    ]


def test_no_mfu_without_a_known_peak():
    """On the CPU the train engine reports throughput but no `mfu` key."""
    from areal_tpu.engine.jax_engine import JaxTrainEngine
    from areal_tpu.models.smoke import smoke_model_config

    eng = SimpleNamespace(
        mesh=None,
        model_config=smoke_model_config(),
        config=SimpleNamespace(is_critic=False),
    )
    batch = {
        "input_ids": np.zeros((2, 8), np.int32),
        "attention_mask": np.ones((2, 8), bool),
    }
    stats = JaxTrainEngine._throughput_stats(eng, batch, step_time=1.0)
    assert stats["n_tokens"] == 16 and "tokens_per_sec_per_chip" in stats
    assert "mfu" not in stats


@pytest.mark.parametrize(
    "mode", ["jax:d1t1+d1", "jax:d2t1+d2", "jax:d1t2+d2", "jax:d1t1+d2"]
)
def test_launcher_gives_every_child_disjoint_chips(mode, monkeypatch):
    import subprocess

    from areal_tpu.api.alloc_mode import AllocationMode
    from areal_tpu.launcher.local import chip_env, plan_chips

    # a host whose chip pairs lie along y: the runtime refuses "2,1,1"
    def probe(cmd, env, **kwargs):
        refused = env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"
        return subprocess.CompletedProcess(cmd, int(refused))

    monkeypatch.setattr(subprocess, "run", probe)
    alloc = AllocationMode.from_str(mode)
    plan = plan_chips(alloc)
    assert len(plan["trainer_0"]) == alloc.train.world_size
    servers = [k for k in plan if k.startswith("decode_server_")]
    assert len(servers) == alloc.gen.data_parallel_size
    assert all(len(plan[k]) == alloc.gen.tp_size for k in servers)
    used = [c for chips in plan.values() for c in chips]
    assert len(used) == len(set(used)), plan  # pairwise disjoint
    # a set of n chips is a whole row/block of the host's chip grid
    assert all(chips[0] % len(chips) == 0 for chips in plan.values()), plan
    envs = [chip_env(chips, 9000 + i) for i, chips in enumerate(plan.values())]
    assert len({e["TPU_VISIBLE_CHIPS"] for e in envs}) == len(envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == len(envs)
    # a pair gets the bounds the runtime accepted, the rest the only ones
    assert [e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs] == [
        {1: "1,1,1", 2: "1,2,1"}[len(chips)] for chips in plan.values()
    ]
    monkeypatch.setattr(
        subprocess, "run", lambda cmd, **kw: subprocess.CompletedProcess(cmd, 1)
    )
    with pytest.raises(RuntimeError, match="none of the bounds"):
        chip_env([2, 3], 9000)
    # only the chip counts probed on the 2x2 host have bounds
    with pytest.raises(ValueError, match="8 chips"):
        chip_env(list(range(8)), 9000)


def test_quant_matmul_pallas_never_gives_way_to_xla():
    from areal_tpu.ops.quant import quantize_absmax
    from areal_tpu.ops.quant_matmul import quant_einsum

    x = jnp.ones((3, 48), jnp.float32)
    wq, ws = quantize_absmax(jnp.ones((48, 40), jnp.float32), axis=(0,))
    with pytest.raises(ValueError, match=r"K=48, N=40"):
        quant_einsum(x, wq, ws, 1, impl="pallas", interpret=True)
