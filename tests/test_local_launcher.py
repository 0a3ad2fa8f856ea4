"""Local launcher process lifecycle (fast: trivial subprocess jobs)."""

import sys
import time

import pytest

from areal_tpu.launcher.base import JobState
from areal_tpu.launcher.local import LocalLauncher
from areal_tpu.utils import name_resolve, names
from areal_tpu.utils.name_resolve import NameResolveConfig


@pytest.fixture()
def launcher(tmp_path):
    l = LocalLauncher("exp", "trial", str(tmp_path))
    yield l
    l.stop_all()


def test_job_completes_and_logs(launcher):
    job = launcher.submit(
        "hello", [sys.executable, "-c", "print('hi from job')"]
    )
    deadline = time.monotonic() + 30
    while job.state is JobState.RUNNING and time.monotonic() < deadline:
        time.sleep(0.1)
    assert job.state is JobState.COMPLETED
    with open(job.log_path) as f:
        assert "hi from job" in f.read()


def test_failure_raises_with_log_tail(launcher):
    launcher.submit(
        "trainer_0",
        [sys.executable, "-c", "import sys; print('boom reason'); sys.exit(3)"],
    )
    with pytest.raises(RuntimeError) as ei:
        launcher.wait(check_interval=0.1)
    assert "boom reason" in str(ei.value)
    assert "rc=3" in str(ei.value)


def test_wait_returns_when_trainers_done(launcher):
    # a long-running "server" plus a quick "trainer": wait() must return
    # when trainers complete even though the server is still alive.
    launcher.submit(
        "decode_server_0", [sys.executable, "-c", "import time; time.sleep(60)"]
    )
    launcher.submit("trainer_0", [sys.executable, "-c", "print('done')"])
    t0 = time.monotonic()
    launcher.wait(check_interval=0.1)
    assert time.monotonic() - t0 < 30
    launcher.stop_all()
    assert launcher.jobs == []


def test_stop_all_kills_process_tree(launcher):
    job = launcher.submit(
        "spin", [sys.executable, "-c", "import time; time.sleep(120)"]
    )
    proc = job.proc
    launcher.stop_all()
    assert proc.poll() is not None


def test_wait_decode_servers_discovery(launcher, tmp_path):
    name_resolve.reconfigure(
        NameResolveConfig(type="nfs", nfs_record_root=str(tmp_path / "nr"))
    )
    try:
        key = names.gen_server("exp", "trial", "10.0.0.1:7001")
        name_resolve.add(key, "10.0.0.1:7001", delete_on_exit=False)
        addrs = launcher.wait_decode_servers(1, timeout=10)
        assert addrs == ["10.0.0.1:7001"]
        with pytest.raises(TimeoutError):
            launcher.wait_decode_servers(2, timeout=1)
    finally:
        name_resolve.reconfigure(NameResolveConfig(type="memory"))


def test_slurm_script_rendering(tmp_path):
    from areal_tpu.launcher.slurm import SlurmJobSpec, render_sbatch_script

    spec = SlurmJobSpec(
        name="trainer",
        cmd="python train.py --config c.yaml",
        n_nodes=4,
        accelerators_per_node=4,
        partition="tpu",
        env={"FOO": "bar"},
        container_image="img:latest",
        container_mounts="/data:/data",
    )
    script = render_sbatch_script(spec, str(tmp_path))
    assert "#SBATCH --nodes=4" in script
    assert "#SBATCH --gres=tpu:4" in script
    assert "#SBATCH --partition=tpu" in script
    assert "export FOO=bar" in script
    assert "AREAL_TPU_NUM_PROCESSES=$SLURM_JOB_NUM_NODES" in script
    assert "--container-image=img:latest" in script
    assert "python train.py --config c.yaml" in script


def test_ray_launcher_gated_without_ray():
    from areal_tpu.launcher.ray import RayLauncher

    l = RayLauncher("exp", "t")
    try:
        import ray  # noqa: F401
        has_ray = True
    except ImportError:
        has_ray = False
    if not has_ray:
        with pytest.raises(RuntimeError, match="requires the `ray` package"):
            l.submit_array("x", lambda rank: rank, 1)


def test_slurm_procid_expands_inside_srun(tmp_path):
    from areal_tpu.launcher.slurm import SlurmJobSpec, render_sbatch_script

    script = render_sbatch_script(
        SlurmJobSpec(name="t", cmd="python x.py", n_nodes=2), str(tmp_path)
    )
    # PROCESS_ID must be set inside the srun-launched shell, not the batch shell.
    assert "export AREAL_TPU_PROCESS_ID=$SLURM_PROCID; python x.py" in script
    batch_part = script.split("srun")[0]
    assert "AREAL_TPU_PROCESS_ID" not in batch_part


def test_ray_coordinator_rendezvous(tmp_path):
    from areal_tpu.launcher.ray import resolve_coordinator

    name_resolve.reconfigure(
        NameResolveConfig(type="nfs", nfs_record_root=str(tmp_path / "nr"))
    )
    try:
        addr0 = resolve_coordinator("exp", "t", 0)
        addr1 = resolve_coordinator("exp", "t", 1, timeout=5)
        assert addr0 == addr1 and ":" in addr0
    finally:
        name_resolve.reconfigure(NameResolveConfig(type="memory"))


def test_job_failure_recoverable_classification(launcher):
    from areal_tpu.launcher.base import JobFailure

    launcher.submit(
        "trainer_0",
        [sys.executable, "-c", "import os, signal; os.kill(os.getpid(), signal.SIGTERM)"],
    )
    with pytest.raises(JobFailure) as ei:
        launcher.wait(check_interval=0.1)
    assert ei.value.recoverable  # SIGTERM'd = preemption-style

def test_wait_no_matching_jobs_returns(launcher):
    launcher.submit(
        "decode_server_0", [sys.executable, "-c", "import time; time.sleep(30)"]
    )
    t0 = time.monotonic()
    launcher.wait(check_interval=0.1)  # no trainer jobs: return, don't spin
    assert time.monotonic() - t0 < 5


def test_servers_build_from_a_seed_only_when_the_config_says_so():
    """The config's `actor.init_from_scratch` decides, not the directory's
    contents: a checkpoint directory whose weights are missing must reach
    the server's loader and fail there, not be served from a seed."""
    import json
    import os

    from areal_tpu.api.cli_args import load_expr_config
    from areal_tpu.launcher.local import LauncherView, scratch_model_arg
    from areal_tpu.models.smoke import SMOKE_MODEL_DICT

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    geometry_only = os.path.join(repo, "examples/configs/qwen2.5-0.5b")
    assert os.listdir(geometry_only) == ["config.json"]
    assert scratch_model_arg(geometry_only, init_from_scratch=False) is None
    built = json.loads(scratch_model_arg(geometry_only, init_from_scratch=True))
    assert (built["hidden_size"], built["vocab_size"]) == (896, 151936)
    for flag in (False, True):
        assert json.loads(scratch_model_arg("", flag)) == SMOKE_MODEL_DICT

    # the launcher's view of a GRPO YAML carries the flag, overrides included
    argv = [
        "--config",
        os.path.join(repo, "examples/configs/qwen2.5_0.5b_grpo_smoke.yaml"),
        "actor.ppo_n_minibatches=1",  # a field only the trainer's class has
    ]
    view, _ = load_expr_config(argv, LauncherView, ignore_unknown=True)
    assert view.actor.init_from_scratch
    view, _ = load_expr_config(
        argv + ["actor.init_from_scratch=false"], LauncherView, ignore_unknown=True
    )
    assert not view.actor.init_from_scratch


@pytest.mark.slow
def test_decoupled_e2e_smoke(tmp_path):
    """Full DECOUPLED-mode E2E, fully offline: run_experiment spawns a
    from-scratch decode server (+ name_resolve registration), then the GRPO
    example as the trainer subprocess, which discovers the server over
    HTTP, rolls out, trains, and pushes weights back over the DCN staging
    path. Two steps must complete and tear down cleanly."""
    import os
    import sys
    import uuid

    from areal_tpu.api.cli_args import GRPOConfig, load_expr_config
    from areal_tpu.launcher.local import run_experiment

    trial = uuid.uuid4().hex[:8]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    overrides = [
        "--config",
        os.path.join(repo, "examples/configs/arith_grpo_smoke.yaml"),
        f"trial_name={trial}",
        f"cluster.fileroot={tmp_path}",
        f"cluster.name_resolve.nfs_record_root={tmp_path}/nr",
        "allocation_mode=jax:d1+d8",
        # minimal workload: the decode server's continuous-batching loop
        # saturates the single CI core, so every extra episode directly
        # starves the trainer's compiles (observed: 16-episode batches push
        # the E2E past 20 min; 4-episode batches finish in ~6)
        "total_train_steps=2",
        "train_dataset.batch_size=2",
        "gconfig.n_samples=2",
        "rollout.consumer_batch_size=4",
        "rollout.max_concurrent_rollouts=8",
        "evaluator.freq_steps=1000",
    ]
    config, _ = load_expr_config(overrides, GRPOConfig)
    entry = [
        sys.executable,
        os.path.join(repo, "examples/gsm8k_grpo.py"),
    ] + overrides
    run_experiment(config, entry, max_restarts=0)
    # the trainer's stats log proves steps ran
    log_dir = os.path.join(str(tmp_path), "logs", config.experiment_name, trial)
    trainer_log = os.path.join(log_dir, "trainer_0.log")
    with open(trainer_log) as f:
        text = f.read()
    assert "global step 1" in text, text[-2000:]
    assert "Traceback" not in text, text[-3000:]
