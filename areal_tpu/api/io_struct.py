"""Core IO dataclasses shared across the framework.

Parity target: areal/api/io_struct.py:21-231 (ModelRequest/ModelResponse/
FinetuneSpec/ParamSpec/WeightUpdateMeta/SaveLoadMeta/RolloutStat/StepInfo).
TPU changes: `WeightUpdateMeta.type` gains "memory" (same-process device_put
resharding, the colocated fast path) and "dcn" (cross-pod transfer server)
alongside "disk"; dtype sizes come from numpy instead of torch.
"""

from __future__ import annotations

import os
import uuid
from dataclasses import dataclass, field
from typing import Any, Literal

import numpy as np

from areal_tpu.api.cli_args import GenerationHyperparameters


@dataclass
class ModelRequest:
    rid: str = field(default_factory=lambda: str(uuid.uuid4()))
    input_ids: list[int] = field(default_factory=list)
    gconfig: GenerationHyperparameters = field(
        default_factory=GenerationHyperparameters
    )
    metadata: dict[str, Any] = field(default_factory=dict)
    tokenizer: Any = None
    # VLM inputs: list of images (bytes/base64/PIL), passed through to the
    # decode backend (parity: io_struct.py:21 ModelRequest.image_data).
    image_data: list[Any] | None = None

    def copy(self) -> "ModelRequest":
        return ModelRequest(
            rid=self.rid,
            input_ids=list(self.input_ids),
            gconfig=self.gconfig.new(),
            metadata=dict(self.metadata),
            tokenizer=self.tokenizer,
            image_data=list(self.image_data) if self.image_data else None,
        )


@dataclass
class ModelResponse:
    input_tokens: list[int] = field(default_factory=list)
    output_tokens: list[int] = field(default_factory=list)
    output_logprobs: list[float] = field(default_factory=list)
    # Weight version that produced each output token — the heart of the
    # async/staleness bookkeeping (reference io_struct.py:48).
    output_versions: list[int] = field(default_factory=list)
    # A block-diffusion model only (empty otherwise): the denoise step of its
    # block at which each output token was revealed, 0-based. With the tokens
    # it rebuilds the state every token was sampled from: at step s of a
    # block, the positions revealed at steps < s (and the prompt's own) held
    # their tokens and every other held the mask token.
    output_reveal_steps: list[int] = field(default_factory=list)
    # "prefill": a prefill-only request (disaggregated fleets) — the KV is
    # parked/exported, no tokens were generated by design.
    stop_reason: Literal["length", "stop", "interrupt", "prefill"] = "stop"
    tokenizer: Any = None

    # statistics
    latency: float = float("inf")
    ttft: float = float("inf")
    itl: list[float] = field(default_factory=list)

    @property
    def input_len(self) -> int:
        return len(self.input_tokens)

    @property
    def output_len(self) -> int:
        return len(self.output_tokens)


@dataclass
class FinetuneSpec:
    total_train_epochs: int
    dataset_size: int
    train_batch_size: int

    @property
    def total_train_steps(self) -> int:
        return self.total_train_epochs * (self.dataset_size // self.train_batch_size)

    @property
    def steps_per_epoch(self) -> int:
        return self.dataset_size // self.train_batch_size


@dataclass
class ParamSpec:
    name: str
    shape: tuple
    dtype: str

    @property
    def size(self) -> int:
        """Param bytes."""
        return int(np.dtype(_np_dtype(self.dtype)).itemsize * np.prod(self.shape))


def _np_dtype(dtype: str) -> str:
    # numpy has no bfloat16; it is 2 bytes like float16 for sizing purposes.
    return {"bfloat16": "float16"}.get(dtype, dtype)


@dataclass
class WeightUpdateMeta:
    """How trainer weights reach the decode engine.

    - "memory": colocated — the trainer hands sharded jax.Arrays to the decode
      engine which `device_put`s them onto its own sharding. Zero-copy when
      shardings agree; the TPU analogue of the reference NCCL broadcast.
    - "disk": save HF-format safetensors shards + name_resolve timestamp
      handshake (identical semantics to the reference's fallback path).
    - "dcn": cross-slice transfer server (learner pod → decode pod).
    """

    type: Literal["disk", "memory", "dcn"] = "memory"
    path: str | None = None
    alloc_mode: Any = None
    transfer_addr: str | None = None
    transfer_port: int = 29500
    group_name: str = "update_weight_group"
    weight_chunked_mem_mb: int = 1024
    use_lora: bool = False
    # Wire dtype for dense matmul kernels on the dcn push: "fp" ships the
    # bf16-cast kernels verbatim (the numerics oracle), "int8" quantizes
    # them ONCE at the producer (per-output-channel symmetric absmax; the
    # `.../q` + `.../scale` leaves halve wire bytes and commit pause).
    # MUST match the consumer's JaxDecodeConfig.weight_dtype — the decode
    # engine serves whatever representation arrives, and the kernel wire
    # names differ between the two.
    weight_dtype: str = "fp"

    @classmethod
    def from_disk(
        cls,
        experiment_name: str,
        trial_name: str,
        file_root: str,
        name: str = "default",
        use_lora: bool = False,
    ) -> "WeightUpdateMeta":
        path = os.path.join(
            file_root,
            "checkpoints",
            experiment_name,
            trial_name,
            name,
            "weight_update",
        )
        return cls(type="disk", path=path, use_lora=use_lora)

    @classmethod
    def from_memory(cls, alloc_mode: Any = None) -> "WeightUpdateMeta":
        return cls(type="memory", alloc_mode=alloc_mode)


@dataclass
class HttpRequest:
    endpoint: str
    payload: dict[str, Any]
    method: str = "POST"


@dataclass
class HttpGenerationResult:
    output_tokens: list[int]
    output_logprobs: list[float]
    stop_reason: str


@dataclass
class SaveLoadMeta:
    path: str
    weight_format: str = "hf"  # "hf" (safetensors) | "orbax"
    with_optim: bool = False
    tokenizer: Any = None
    base_model_path: str | None = None


@dataclass
class RolloutStat:
    submitted: int = 0
    accepted: int = 0
    running: int = 0


@dataclass
class StepInfo:
    epoch: int
    epoch_step: int
    global_step: int
    steps_per_epoch: int

    def next(self) -> "StepInfo":
        last_in_epoch = self.epoch_step == self.steps_per_epoch - 1
        return StepInfo(
            epoch=self.epoch + last_in_epoch,
            epoch_step=0 if last_in_epoch else self.epoch_step + 1,
            global_step=self.global_step + 1,
            steps_per_epoch=self.steps_per_epoch,
        )
