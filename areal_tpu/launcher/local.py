"""Local launcher: decode servers + trainer processes on one host.

Parity: areal/launcher/local.py:81 LocalLauncher — spawns LLM-server
subprocesses and N trainer processes, allocates accelerators, tails logs,
kills the whole tree on failure, and auto-restarts the experiment after
RECOVER_TIME_INTERVAL up to `recover_retries` times.

TPU translation: the "LLM server" is our decode server
(areal_tpu.launcher.decode_server), accelerator allocation is by TPU chip
visibility (`plan_chips` / `chip_env`: every child of a decoupled
allocation, the trainer included, owns a disjoint chip set, because a chip
belongs to one process at a time) rather than CUDA_VISIBLE_DEVICES, and
trainer ranks are JAX processes (AREAL_TPU process env + jax.distributed)
rather than torchrun ranks. Discovery stays name_resolve: servers
self-register under names.gen_servers. This process never initialises a
JAX backend: a parent that holds the chips would starve its children.

Usage (mirrors `python -m areal.launcher.local entry.py --config c.yaml`):

    python -m areal_tpu.launcher.local entry.py --config cfg.yaml [k=v ...]
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

from areal_tpu.api.alloc_mode import AllocationMode, AllocationType
from areal_tpu.api.cli_args import (
    BaseExperimentConfig,
    TrainEngineConfig,
    load_expr_config,
)
from areal_tpu.launcher.base import (
    JobFailure,
    JobInfo,
    JobState,
    kill_process_tree,
)
from areal_tpu.utils import logging, name_resolve, names
from areal_tpu.utils.network import find_free_ports, gethostip

logger = logging.getLogger("local_launcher")

RECOVER_TIME_INTERVAL = 10.0  # parity: local.py:58

# libtpu's TPU_CHIPS_PER_PROCESS_BOUNDS (x,y,z of the chip grid a process
# sees) for a process that owns n chips of a 2x2 host. Which way a pair of
# chips lies differs from host to host: chips 2,3 were an x-pair on one v5e
# host and a y-pair on the next, and the runtime exits 1 on the wrong one
# (PERF.md, PR 21). So `chip_env` tries a pair both ways in a throwaway
# child. No larger host was probed: any other count is refused.
_CHIP_BOUNDS = {1: ("1,1,1",), 2: ("2,1,1", "1,2,1"), 4: ("2,2,1",)}
# every probe on the chip answered, ok or refused, within 12-21 s; a child
# still silent after this is hung, and subprocess.run raises
_PROBE_TIMEOUT_S = 120


def plan_chips(alloc: AllocationMode) -> dict[str, list[int]]:
    """Chips of this host for every child of a decoupled allocation, by job
    name: each server replica takes gen-tp chips, the trainer the train
    world size. The sets are pairwise disjoint, and a set of n chips starts
    at a multiple of n (a two-chip process must own two neighbours of the
    host's chip grid: chips 2k and 2k+1 were neighbours on every host
    probed), which can leave a chip unused."""
    gen_tp = alloc.gen.tp_size
    sizes = {
        f"decode_server_{i}": gen_tp
        for i in range(alloc.gen.data_parallel_size)
    }
    sizes["trainer_0"] = alloc.train.world_size
    plan, free = {}, 0
    for name, n in sizes.items():
        first = -(-free // n) * n
        plan[name] = list(range(first, first + n))
        free = first + n
    return plan


def _chip_env(chips: list[int], port: int, bounds: str) -> dict[str, str]:
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port),
    }


def chip_env(chips: list[int], port: int) -> dict[str, str]:
    """Environment that confines one child's TPU runtime to `chips` as a
    process of its own, with its own mesh controller on `port`. Visibility
    alone is not enough: without the bounds and the port, the second child
    to start fails on libtpu's multi-process lockfile. Where the bounds
    depend on the host, each candidate is tried in a child that starts the
    runtime and exits, before the real child is given the chips."""
    candidates = _CHIP_BOUNDS.get(len(chips))
    if candidates is None:
        raise ValueError(
            f"cannot give one process {len(chips)} chips {chips} of a host: "
            f"supported counts are {sorted(_CHIP_BOUNDS)}"
        )
    if len(candidates) == 1:
        return _chip_env(chips, port, candidates[0])
    for bounds in candidates:
        env = _chip_env(chips, port, bounds)
        probe = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            env={**os.environ, **env},
            capture_output=True,
            timeout=_PROBE_TIMEOUT_S,
        )
        logger.info(
            f"chips {chips} as one process, bounds {bounds}: "
            f"{'ok' if probe.returncode == 0 else 'refused'}"
        )
        if probe.returncode == 0:
            return env
    raise RuntimeError(
        f"the TPU runtime gave chips {chips} to one process under none of the "
        f"bounds {candidates}; its own log is under /tmp/tpu_logs"
    )


def scratch_model_arg(model_path: str, init_from_scratch: bool) -> str | None:
    """`--scratch-model` JSON when the servers are to build their weights
    from a seed: for the offline sentinels (the canonical tiny model), and
    for a directory's config.json only when the experiment says
    `actor.init_from_scratch`. None otherwise: the server loads
    `model_path`, and a directory that holds no weights fails there."""
    from areal_tpu.models.smoke import OFFLINE_SENTINELS, SMOKE_MODEL_DICT

    if model_path in OFFLINE_SENTINELS:
        return json.dumps(SMOKE_MODEL_DICT)
    if init_from_scratch:
        from areal_tpu.models.qwen2 import ModelConfig

        return json.dumps(
            dataclasses.asdict(ModelConfig.from_hf_config(model_path))
        )
    return None


class DecodeServerHandle:
    """supervisor.ReplicaHandle over a LocalLauncher subprocess: the
    addr the replica registered under, plus a kill that reaps the whole
    process tree and drops the job from the launcher's watch list (a
    supervisor-initiated kill must not trip _raise_on_failure)."""

    def __init__(self, launcher: "LocalLauncher", job: JobInfo, addr: str):
        self._launcher = launcher
        self._job = job
        self.addr = addr

    def kill(self) -> None:
        if self._job.proc is not None:
            kill_process_tree(self._job.proc)
        try:
            self._launcher.jobs.remove(self._job)
        except ValueError:
            pass


class LocalLauncher:
    def __init__(self, experiment_name: str, trial_name: str, fileroot: str):
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.fileroot = fileroot
        self.jobs: list[JobInfo] = []

    # -- paths ----------------------------------------------------------
    def log_dir(self) -> str:
        d = os.path.join(
            self.fileroot, "logs", self.experiment_name, self.trial_name
        )
        os.makedirs(d, exist_ok=True)
        return d

    # -- submission -----------------------------------------------------
    def submit(
        self,
        name: str,
        cmd: list[str],
        env: dict[str, str] | None = None,
    ) -> JobInfo:
        import subprocess

        log_path = os.path.join(self.log_dir(), f"{name}.log")
        logf = open(log_path, "ab")
        full_env = dict(os.environ)
        full_env.update(env or {})
        proc = subprocess.Popen(
            cmd,
            stdout=logf,
            stderr=subprocess.STDOUT,
            env=full_env,
            start_new_session=True,  # own process group → clean tree kill
        )
        job = JobInfo(name=name, cmd=cmd, proc=proc, log_path=log_path)
        self.jobs.append(job)
        logger.info(f"launched {name}: pid={proc.pid} log={log_path}")
        return job

    def submit_decode_server(
        self,
        server_idx: int,
        model_path: str,
        *,
        port: int | None = None,
        extra_args: list[str] | None = None,
        env: dict[str, str] | None = None,
    ) -> JobInfo:
        port = port or find_free_ports(1)[0]
        # Lower CPU priority: the decode engine's continuous-batching loop
        # saturates whatever cores it gets (by design); when servers and the
        # trainer share a host's CPUs (colocated smoke / CI), the trainer's
        # XLA compiles must win or the first training step starves behind
        # rollout decode. On real deployments each side owns its chips and
        # nice is a no-op.
        cmd = [
            "nice",
            "-n",
            "10",
            sys.executable,
            "-m",
            "areal_tpu.launcher.decode_server",
            "--model-path",
            model_path,
            "--host",
            "0.0.0.0",
            "--port",
            str(port),
            "--experiment-name",
            self.experiment_name,
            "--trial-name",
            self.trial_name,
            "--server-id",
            f"{gethostip()}:{port}",
        ] + (extra_args or [])
        return self.submit(f"decode_server_{server_idx}", cmd, env=env)

    def spawn_decode_server(
        self,
        role: str = "unified",
        *,
        model_path: str,
        extra_args: list[str] | None = None,
        env: dict[str, str] | None = None,
        timeout: float = 300.0,
    ) -> "DecodeServerHandle":
        """Launcher seam for the fleet supervisor
        (launcher/supervisor.py): spawn ONE decode-server subprocess with
        the given role, block until it self-registers in name_resolve,
        and return a handle exposing the (addr, kill) surface the
        supervisor drives. Raises on spawn/registration failure — the
        supervisor's jittered-backoff retry and crash-loop escalation own
        that outcome."""
        port = find_free_ports(1)[0]
        addr = f"{gethostip()}:{port}"
        args = list(extra_args or [])
        if role != "unified":
            args += ["--role", role]
        job = self.submit_decode_server(
            len(self.jobs),
            model_path,
            port=port,
            extra_args=args,
            env=env,
        )
        key = names.gen_server(self.experiment_name, self.trial_name, addr)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if job.state is JobState.FAILED:
                break
            try:
                if name_resolve.get(key) == addr:
                    return DecodeServerHandle(self, job, addr)
            except Exception as e:  # noqa: BLE001 — not registered yet
                logger.debug(f"spawned server {addr} pending: {e!r}")
            time.sleep(0.5)
        # failed or timed out: reap the subprocess before reporting
        if job.proc is not None:
            kill_process_tree(job.proc)
        try:
            self.jobs.remove(job)
        except ValueError:
            pass
        raise JobFailure(
            f"decode server {addr} (role={role}) did not register "
            f"within {timeout}s",
            recoverable=True,
        )

    def wait_decode_servers(self, count: int, timeout: float = 300.0) -> list[str]:
        """Block until `count` servers registered in name_resolve."""
        key = names.gen_servers(self.experiment_name, self.trial_name)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self._raise_on_failure()
            try:
                addrs = name_resolve.get_subtree(key)
            except Exception as e:  # noqa: BLE001 — not registered yet
                logger.debug(f"server discovery pending: {e!r}")
                addrs = []
            if len(addrs) >= count:
                return list(addrs)
            time.sleep(1.0)
        raise TimeoutError(
            f"{count} decode servers did not register within {timeout}s"
        )

    def submit_trainers(
        self,
        entrypoint: list[str],
        n_procs: int,
        env: dict[str, str] | None = None,
    ) -> list[JobInfo]:
        """Spawn trainer processes with jax.distributed-style env. On a
        single TPU host n_procs is typically 1 (one process drives all local
        chips under SPMD)."""
        coord_port = find_free_ports(1)[0]
        jobs = []
        for rank in range(n_procs):
            proc_env = {
                "AREAL_EXPERIMENT_NAME": self.experiment_name,
                "AREAL_TRIAL_NAME": self.trial_name,
                "AREAL_TPU_NUM_PROCESSES": str(n_procs),
                "AREAL_TPU_PROCESS_ID": str(rank),
                "AREAL_TPU_COORDINATOR": f"{gethostip()}:{coord_port}",
                **(env or {}),
            }
            jobs.append(
                self.submit(f"trainer_{rank}", list(entrypoint), env=proc_env)
            )
        return jobs

    # -- supervision ----------------------------------------------------
    def _raise_on_failure(self) -> None:
        for job in self.jobs:
            if job.state is JobState.FAILED:
                tail = ""
                if job.log_path and os.path.exists(job.log_path):
                    with open(job.log_path, "rb") as f:
                        f.seek(max(0, os.path.getsize(job.log_path) - 4096))
                        tail = f.read().decode(errors="replace")
                raise JobFailure(
                    f"job {job.name} failed rc={job.returncode}\n"
                    f"--- last log lines ---\n{tail}",
                    recoverable=job.recoverable(),
                )

    def poll(self) -> dict[str, JobState]:
        return {j.name: j.state for j in self.jobs}

    def wait(
        self,
        check_interval: float = 2.0,
        until: str = "trainers",  # "trainers" | "all"
    ) -> None:
        """Block until trainer jobs finish (servers are then torn down) or
        raise on the first failed job."""
        while True:
            self._raise_on_failure()
            watched = [
                j
                for j in self.jobs
                if until == "all" or j.name.startswith("trainer")
            ]
            if not watched:
                return  # nothing to wait on — don't spin forever
            if all(j.state is JobState.COMPLETED for j in watched):
                return
            time.sleep(check_interval)

    def stop_all(self) -> None:
        for job in reversed(self.jobs):
            if job.proc is not None:
                kill_process_tree(job.proc)
        self.jobs.clear()


def run_experiment(
    config,
    entrypoint: list[str],
    *,
    max_restarts: int = 0,
) -> None:
    """Launch servers+trainers per the allocation mode; auto-restart the
    whole experiment on recoverable failure (parity: local.py recover loop)."""
    alloc = AllocationMode.from_str(config.allocation_mode)
    # One shared discovery store for launcher + servers + trainers: the
    # launcher applies the experiment's name_resolve config and ships it to
    # every subprocess via env (each process's module default is otherwise
    # an in-process memory store that nobody else can see).
    if (
        alloc.type_ == AllocationType.DECOUPLED_TRAIN
        and config.cluster.name_resolve.type == "memory"
    ):
        raise ValueError(
            "decoupled allocation needs a CROSS-PROCESS name_resolve backend "
            "(nfs/etcd3/ray); type='memory' is per-process and the trainer "
            "could never discover the decode servers"
        )
    name_resolve.reconfigure(config.cluster.name_resolve)
    nr_env = name_resolve.to_env(config.cluster.name_resolve)
    launcher = LocalLauncher(
        config.experiment_name, config.trial_name, config.cluster.fileroot
    )
    model_path = getattr(config.decode, "model_path", "") or config.tokenizer_path
    init_from_scratch = config.actor.init_from_scratch
    attempt = 0
    while True:
        try:
            # Stale registrations from a previous (crashed) attempt would
            # satisfy wait_decode_servers with dead ip:port records —
            # clear the subtree so only THIS attempt's servers count.
            try:
                name_resolve.clear_subtree(
                    names.gen_servers(config.experiment_name, config.trial_name)
                )
            except Exception as e:  # noqa: BLE001 — nothing registered yet
                logger.debug(f"stale-registration clear skipped: {e!r}")
            n_servers = (
                alloc.gen.data_parallel_size
                if alloc.type_ in (AllocationType.DECOUPLED_TRAIN,)
                else 0
            )
            gen_tp = alloc.gen.tp_size if alloc.gen is not None else 1
            # a CPU run (JAX_PLATFORMS=cpu, which every child inherits) has
            # no chips to split
            on_cpu = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
            chips = plan_chips(alloc) if n_servers and not on_cpu else {}
            chip_envs = {
                name: chip_env(c, port)
                for (name, c), port in zip(
                    chips.items(), find_free_ports(len(chips))
                )
            }
            # Disaggregated role fleet (launcher.prefill_replicas): the
            # first K replicas launch as prefill (compute-bound, stream KV
            # out), the rest as decode (memory-bound, import + resume).
            n_prefill = int(
                getattr(config.launcher, "prefill_replicas", 0) or 0
            )
            if n_prefill and n_prefill >= n_servers:
                raise ValueError(
                    f"launcher.prefill_replicas={n_prefill} must leave at "
                    f"least one decode replica (gen dp = {n_servers})"
                )
            for i in range(n_servers):
                env = dict(chip_envs.get(f"decode_server_{i}", {}))
                extra = ["--tp-size", str(gen_tp)] if gen_tp > 1 else []
                # forward the experiment's decode config — without these the
                # server silently runs its DEFAULTS (32k context, 64 slots,
                # 128-token chunks), which on small smoke runs means orders-
                # of-magnitude more compute per chunk than configured
                dec = config.decode
                extra += [
                    "--context-length", str(dec.context_length),
                    "--max-running-requests", str(dec.max_running_requests),
                    "--new-tokens-per-chunk", str(dec.new_tokens_per_chunk),
                    "--dtype", dec.dtype,
                    "--seed", str(dec.random_seed),
                ]
                if n_prefill:
                    role = "prefill" if i < n_prefill else "decode"
                    extra += ["--role", role]
                    if role == "decode" and float(
                        getattr(dec, "kv_host_pool_mb", 0.0)
                    ) > 0:
                        extra += [
                            "--kv-host-pool-mb", str(dec.kv_host_pool_mb)
                        ]
                elif getattr(dec, "role", "unified") != "unified":
                    extra += ["--role", dec.role]
                scratch = scratch_model_arg(model_path, init_from_scratch)
                if scratch is not None:
                    # no checkpoint to load: serve the geometry from a
                    # seed (the trainer pushes its weights at step 1), so
                    # the DECOUPLED path runs with no HF access
                    extra += ["--scratch-model", scratch]
                env.update(nr_env)
                launcher.submit_decode_server(
                    i,
                    model_path,
                    extra_args=extra,
                    env=env,
                )
            if n_servers:
                launcher.wait_decode_servers(n_servers)
            launcher.submit_trainers(
                entrypoint,
                n_procs=1,
                env={**chip_envs.get("trainer_0", {}), **nr_env},
            )
            launcher.wait()
            launcher.stop_all()  # trainers done: tear down decode servers
            return
        except JobFailure as e:
            launcher.stop_all()
            attempt += 1
            if attempt > max_restarts or not e.recoverable:
                raise
            logger.warning(
                f"experiment failed ({e}); restart {attempt}/{max_restarts} "
                f"in {RECOVER_TIME_INTERVAL}s"
            )
            time.sleep(RECOVER_TIME_INTERVAL)
        except BaseException:
            launcher.stop_all()
            raise


@dataclasses.dataclass
class LauncherView(BaseExperimentConfig):
    """What the launcher reads of an experiment config, whatever its class:
    the base fields, and of the actor whether its weights come from a
    checkpoint or a seed (the decode servers must start from the same).
    The trainer subprocess re-parses the full subclass config."""

    actor: TrainEngineConfig = dataclasses.field(
        default_factory=TrainEngineConfig
    )


def main(argv: list[str] | None = None) -> None:
    """CLI: python -m areal_tpu.launcher.local entry.py --config cfg.yaml [k=v]"""
    argv = list(sys.argv[1:] if argv is None else argv)
    assert argv and argv[0].endswith(".py"), (
        "usage: python -m areal_tpu.launcher.local entry.py --config cfg.yaml"
    )
    entry = argv[0]
    config, _ = load_expr_config(argv[1:], LauncherView, ignore_unknown=True)
    max_restarts = (
        config.recover.retries
        if config.recover.mode in ("auto", "fault")
        else 0
    )
    run_experiment(
        config,
        [sys.executable, entry] + argv[1:],
        max_restarts=max_restarts,
    )


if __name__ == "__main__":
    main()
