"""Engine contracts: TrainEngine and InferenceEngine.

Parity target: areal/api/engine_api.py:41 (TrainEngine), :347
(InferenceEngine). Method names are preserved so reference training scripts
port mechanically. Semantics differ where SPMD-on-TPU differs from
one-process-per-GPU torch:

- The reference runs N trainer processes (torchrun) that each own a model
  shard and coordinate via NCCL process groups. Here ONE controller process
  per host drives a global jit program over a jax.sharding.Mesh; "process
  group" methods therefore describe mesh topology rather than communicator
  handles. Multi-host execution uses jax.distributed with the same code.
- `train_batch`'s contract is unchanged: loss_fn over packed 1-D inputs,
  loss_weight_fn for global normalization across micro-batches
  (engine_api.py:242-274).
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    ModelRequest,
    ModelResponse,
    SaveLoadMeta,
    WeightUpdateMeta,
)

if TYPE_CHECKING:
    from areal_tpu.api.workflow_api import RolloutWorkflow


@dataclass
class Scheduling:
    """Resource requirements for scheduling one engine worker
    (parity: areal/api/engine_api.py:24)."""

    cpu: int = 4
    gpu: int = 0
    tpu: int = 1
    mem: int = 32 * 1024  # MB
    port_count: int = 2
    env_vars: dict[str, str] = field(default_factory=dict)


class TrainEngine(abc.ABC):
    """SPMD training engine contract (parity: engine_api.py:41)."""

    # -- lifecycle ------------------------------------------------------
    def create_process_group(
        self, parallel_strategy: ParallelStrategy | None = None
    ) -> None:
        """Initialise the device mesh for `parallel_strategy` (and
        jax.distributed in multi-host deployments)."""
        raise NotImplementedError()

    def initialize(
        self,
        addr: str | None = None,
        ft_spec: FinetuneSpec | None = None,
    ) -> None:
        """Load the model onto the mesh and build the optimizer."""
        raise NotImplementedError()

    def destroy(self) -> None:
        """Release device buffers."""

    # -- topology introspection ----------------------------------------
    @property
    def data_parallel_rank(self) -> int:
        raise NotImplementedError()

    @property
    def data_parallel_world_size(self) -> int:
        raise NotImplementedError()

    @property
    def is_data_parallel_head(self) -> bool:
        raise NotImplementedError()

    def get_scheduling_config(self) -> Scheduling:
        return Scheduling()

    # -- mode -----------------------------------------------------------
    def train(self, mode: bool = True):
        """Toggle train mode (dropout etc.; most TPU configs disable dropout)."""
        return self

    def eval(self):
        return self.train(False)

    # -- weights --------------------------------------------------------
    def update_weights(self, meta: WeightUpdateMeta) -> None:
        """Push current weights to the connected inference engine."""
        raise NotImplementedError()

    def update_weights_async(self, meta: WeightUpdateMeta | None = None):
        """Start a weight push WITHOUT blocking the train loop: the stage
        phase (host gather + bucket streaming, for transports that support
        staging) runs on a background thread while the caller keeps
        training. Returns a handle with `join()` (wait for staging),
        `commit()` (join, then enter the pause window and commit — the
        synchronization point the caller chooses) and `abort()`. Engines
        whose transport has no stage/commit split may run the whole push on
        the background thread and make commit() a bare join."""
        raise NotImplementedError()

    def connect_engine(self, engine: "InferenceEngine", meta: WeightUpdateMeta):
        """Wire an inference engine for weight updates + rollout dispatch."""
        raise NotImplementedError()

    def set_version(self, version: int) -> None:
        raise NotImplementedError()

    def get_version(self) -> int:
        raise NotImplementedError()

    def save(self, meta: SaveLoadMeta) -> None:
        raise NotImplementedError()

    def load(self, meta: SaveLoadMeta) -> None:
        raise NotImplementedError()

    def step_lr_scheduler(self) -> None:
        """Advance the LR schedule one step (no-op when the schedule is
        driven by the optimizer step count, the optax default)."""

    # -- compute --------------------------------------------------------
    def train_batch(
        self,
        input_: dict[str, Any],
        loss_fn: Callable[[Any, dict[str, Any]], Any],
        loss_weight_fn: Callable[[dict[str, Any]], Any],
    ) -> dict[str, float]:
        """One optimizer step over a padded batch, internally split into
        FFD-balanced packed micro-batches. loss_fn consumes packed 1-D
        inputs; loss_weight_fn supplies each micro-batch's weight for global
        loss normalization."""
        raise NotImplementedError()

    def eval_batch(
        self,
        input_: dict[str, Any],
        loss_fn: Callable[[Any, dict[str, Any]], Any],
        loss_weight_fn: Callable[[dict[str, Any]], Any],
    ):
        raise NotImplementedError()

    def forward(
        self,
        input_: dict[str, Any],
        output_seqlens: list[int] | None = None,
        post_hook: Callable[[Any, dict[str, Any]], Any] | None = None,
        aggregate_fn: Callable[[list[Any]], Any] | None = None,
    ):
        """Gradient-free forward over micro-batches; results are un-padded,
        re-ordered to input order, and aggregated."""
        raise NotImplementedError()


class EngineDeadError(RuntimeError):
    """The engine's serving loop has died: no request can succeed until it
    is re-initialised, so callers fail at once instead of retrying."""


class InferenceEngine(abc.ABC):
    """Rollout/generation engine contract (parity: engine_api.py:347)."""

    def initialize(
        self,
        addr: str | None = None,
        ft_spec: FinetuneSpec | None = None,
        train_data_parallel_size: int | None = None,
    ):
        raise NotImplementedError()

    def destroy(self):
        pass

    # -- generation -----------------------------------------------------
    async def agenerate(self, req: ModelRequest) -> ModelResponse:
        """Asynchronously generate a response for one request."""
        raise NotImplementedError()

    # -- rollout queue --------------------------------------------------
    def submit(
        self,
        data: dict[str, Any],
        workflow: "RolloutWorkflow | None" = None,
        workflow_builder: Callable | None = None,
        should_accept: Callable | None = None,
    ) -> None:
        raise NotImplementedError()

    def wait(self, count: int, timeout: float | None = None) -> dict[str, Any]:
        raise NotImplementedError()

    def rollout_batch(
        self,
        data: list[dict[str, Any]],
        workflow: "RolloutWorkflow | None" = None,
        workflow_builder: Callable | None = None,
        should_accept: Callable | None = None,
    ) -> dict[str, Any]:
        raise NotImplementedError()

    def prepare_batch(
        self,
        dataloader,
        workflow: "RolloutWorkflow | None" = None,
        workflow_builder: Callable | None = None,
        should_accept: Callable | None = None,
    ) -> dict[str, Any]:
        raise NotImplementedError()

    # -- flow control ---------------------------------------------------
    def pause(self):
        """Stop submitting new rollouts (weight-update window)."""
        raise NotImplementedError()

    def resume(self):
        raise NotImplementedError()

    def pause_generation(self):
        """Interrupt in-flight generation on the servers."""

    def continue_generation(self):
        pass

    # -- weight updates -------------------------------------------------
    def init_weights_update_group(self, meta: WeightUpdateMeta):
        pass

    def update_weights_from_distributed(self, meta: WeightUpdateMeta, *args, **kwargs):
        raise NotImplementedError()

    def update_weights_from_disk(self, meta: WeightUpdateMeta):
        raise NotImplementedError()

    def update_weights_from_tensor(
        self,
        named: dict,
        version: int | None = None,
        chunk_mb: float = 512,
        **kwargs,
    ) -> None:
        """Install host tensors keyed by `/`-joined param-tree path (the
        "dcn" in-memory push; see areal_tpu/core/weight_transfer.py).
        `named` may also be an iterable of (name, array) pairs for
        pipelined producers. Implementations may accept `lora_scale` (LoRA
        delta push) and `overlap`/`inflight` (staged-push controls)."""
        raise NotImplementedError()

    # -- staged weight sync (optional; transports with a stage/commit
    #    split — the HTTP "dcn" path — implement these so staging overlaps
    #    live generation and only the commit pays a pause) ---------------
    def stage_weights(
        self,
        named,
        push_id: str | None = None,
        chunk_mb: float = 512,
        inflight: int | None = None,
    ) -> str:
        """Stream weight buckets into server-side staging WITHOUT pausing
        generation; returns the push_id to commit or abort."""
        raise NotImplementedError()

    def commit_staged(
        self,
        push_id: str,
        version: int | None = None,
        lora_scale: float | None = None,
    ) -> None:
        """Atomically install the staged weights (the only pause window)."""
        raise NotImplementedError()

    def abort_push(self, push_id: str) -> None:
        """Drop server-side staging for a failed/abandoned push."""
        raise NotImplementedError()

    def set_version(self, version: int) -> None:
        raise NotImplementedError()

    def get_version(self) -> int:
        raise NotImplementedError()
