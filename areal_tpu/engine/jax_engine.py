"""JaxTrainEngine: the GSPMD/pjit training backend.

Parity target: areal/engine/fsdp_engine.py:65 (FSDPEngine) +
areal/engine/base_hf_engine.py:46 (BaseHFEngine). One engine replaces both
torch backends (FSDP2+DTensor and Megatron): parameter sharding, tensor
parallelism, sequence parallelism and grad synchronisation are all expressed
as NamedShardings over one mesh, and XLA emits the collectives that
FSDP2's gather/scatter hooks, DTensor's TP plan, Ulysses' all-to-alls and
Megatron's DDP allreduce perform by hand.

Design (TPU-first):
- Single-controller SPMD: one Python process per host drives a global jit
  program; there is no per-GPU process, no torchrun, no NCCL group setup.
  create_process_group() builds the mesh (and calls
  jax.distributed.initialize on multi-host).
- train_batch keeps the reference contract (engine_api.py:242-274): split a
  padded batch into FFD-balanced packed micro-batches, per-micro-batch
  backward with loss_weight_fn-weighted gradient accumulation, ONE optimizer
  step with global grad-norm clipping.
- Two jitted programs per loss function: `_grad_step` (value_and_grad over
  the packed forward) and `_apply_update` (clip + optax update), both with
  donated buffers. Micro-batch token streams are bucketed
  (pad_packed_tensor_dict) so recompiles are rare.
- Optimizer: optax AdamW with fp32 moments (the reference's
  AnyPrecisionAdamW, areal/utils/fsdp/__init__.py) + warmup/cosine/linear
  schedules; bf16 params, fp32 grad accumulation
  (grad_reduce_dtype).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from areal_tpu.api.alloc_mode import ParallelStrategy
from areal_tpu.api.cli_args import OptimizerConfig, TrainEngineConfig
from areal_tpu.api.engine_api import InferenceEngine, TrainEngine
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    SaveLoadMeta,
    WeightUpdateMeta,
)
from areal_tpu.models import hf_io
from areal_tpu.models.qwen2 import (
    LMHead,
    ModelConfig,
    forward as model_forward,
    init_lora_params,
    init_params,
    lora_param_axes,
    merge_lora,
    param_logical_axes,
    resolve_attn_impl,
    segment_ids_from_cu_seqlens,
)
from areal_tpu.ops.flash_attention import live_block_counts
from areal_tpu.ops.ring_attention import cp_ring_shards, zigzag_eligible
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.utils import hbm, logging, name_resolve, names, perf_tracer
from areal_tpu.utils.data import (
    MicroBatchList,
    split_padded_tensor_dict_into_mb_list,
    unpack_sequence,
    zigzag_indices,
)

logger = logging.getLogger("jax_engine")


def _memory_analysis_dict(compiled) -> dict:
    """Per-program XLA memory analysis (bytes); {} where the backend does
    not expose one."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.debug(f"memory_analysis unavailable: {e!r}")
        return {}
    if ma is None:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out

# Keys that carry per-token values and therefore ride along into the packed
# device micro-batch. Anything else (per-sequence scalars, metadata) stays on
# host — loss functions only consume token-aligned arrays.
_TOKEN_KEYS_HINT = (
    "input_ids",
    "loss_mask",
    "logprobs",
    "prox_logp",
    "ref_logp",
    "advantages",
    "old_logp",
    "versions",
    "labels",
    "values",
    "returns",
    "old_values",
)


def make_lr_schedule(cfg: OptimizerConfig, total_steps: int) -> optax.Schedule:
    warmup = max(int(cfg.warmup_steps_proportion * total_steps), 1)
    decay_steps = max(total_steps - warmup, 1)
    end = cfg.lr * cfg.min_lr_ratio
    if cfg.lr_scheduler_type == "cosine":
        decay = optax.cosine_decay_schedule(
            cfg.lr, decay_steps=decay_steps, alpha=cfg.min_lr_ratio
        )
    elif cfg.lr_scheduler_type == "linear":
        decay = optax.linear_schedule(cfg.lr, end, transition_steps=decay_steps)
    elif cfg.lr_scheduler_type == "constant":
        decay = optax.constant_schedule(cfg.lr)
    else:
        raise ValueError(f"unknown lr_scheduler_type {cfg.lr_scheduler_type}")
    return optax.join_schedules(
        [optax.linear_schedule(0.0, cfg.lr, transition_steps=warmup), decay],
        boundaries=[warmup],
    )


def make_optimizer(
    cfg: OptimizerConfig, total_steps: int
) -> tuple[optax.GradientTransformation, optax.Schedule]:
    schedule = make_lr_schedule(cfg, total_steps)
    if cfg.type == "adamw":
        opt = optax.adamw(
            learning_rate=schedule,
            b1=cfg.beta1,
            b2=cfg.beta2,
            eps=cfg.eps,
            weight_decay=cfg.weight_decay,
            mu_dtype=jnp.dtype(cfg.moment_dtype),
            # decay only matrices; vectors (norms, biases) are excluded —
            # standard practice matching torch's no_decay param groups
            mask=lambda params: jax.tree.map(lambda p: p.ndim > 1, params),
        )
    elif cfg.type == "sgd":
        opt = optax.sgd(learning_rate=schedule)
    else:
        raise ValueError(f"unknown optimizer type {cfg.type}")
    return opt, schedule


def zero1_extend_sharding(
    sharding: jax.sharding.NamedSharding,
    shape: tuple[int, ...],
    mesh: jax.sharding.Mesh,
) -> jax.sharding.NamedSharding:
    """ZeRO-1 spec for an optimizer-state / gradient leaf: additionally
    shard over the dp axis (arXiv:2004.13336 — each dp rank owns 1/dp of
    the moments and of the update computation).

    Leaves whose sharding already uses dp anywhere (fsdp "embed" rule) are
    left alone — a mesh axis may shard at most one dim. Otherwise dp is
    appended to the first dim it divides evenly (on top of whatever axes
    already shard that dim); leaves too small to split stay as they are
    (scalars, tiny norm vectors on awkward meshes).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    dp = mesh.shape.get(mesh_lib.AXIS_DP, 1)
    if dp <= 1 or not shape:
        return sharding
    spec = tuple(sharding.spec)
    used: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        used.update((entry,) if isinstance(entry, str) else entry)
    if mesh_lib.AXIS_DP in used:
        return sharding
    new_spec = list(spec) + [None] * (len(shape) - len(spec))
    for i, dim in enumerate(shape):
        entry = new_spec[i]
        group = (
            ()
            if entry is None
            else ((entry,) if isinstance(entry, str) else tuple(entry))
        )
        existing = 1
        for a in group:
            existing *= mesh.shape.get(a, 1)
        if dim % (existing * dp) == 0:
            new_spec[i] = group + (mesh_lib.AXIS_DP,) if group else mesh_lib.AXIS_DP
            return NamedSharding(mesh, PartitionSpec(*new_spec))
    return sharding


def opt_state_sharding(
    optimizer: optax.GradientTransformation,
    trainable_params,
    trainable_shardings,
    mesh: jax.sharding.Mesh,
    *,
    zero1: bool = False,
):
    """Shard optimizer moments like their parameters (plus ZeRO-1 dp split).

    optax states embed *copies of the param tree* (ScaleByAdamState.mu/nu
    etc.), so every moment leaf's key path ends with the key path of the
    param it mirrors. Matching on that path suffix is exact — unlike shape
    matching, two distinct params with equal shapes (e.g. gate and up
    projections) can never swap shardings. Leaves whose path matches no
    param (step counters) are replicated.

    This is THE one builder for opt-state shardings — `initialize`,
    `_get_apply_update`, orbax restore and the plan check all go through
    the engine's cached `_opt_state_shardings()` wrapper around it, so a
    schedule switch or a restore can never silently re-replicate moments.

    With `zero1`, every moment leaf is additionally dp-sharded
    (`zero1_extend_sharding`): grads arrive reduce-scattered, the update
    math runs on 1/dp of the state per rank, and the param out_shardings
    all-gather the result — XLA emits the collectives from the shardings
    alone, the update code is unchanged.
    """
    shape = jax.eval_shape(optimizer.init, trainable_params)
    param_paths = {
        tuple(str(k) for k in path): shard
        for path, shard in jax.tree_util.tree_leaves_with_path(
            trainable_shardings
        )
    }
    repl = mesh_lib.replicated(mesh)

    def assign(path, leaf):
        keys = tuple(str(k) for k in path)
        for i in range(len(keys)):
            hit = param_paths.get(keys[i:])
            if hit is not None:
                if zero1:
                    return zero1_extend_sharding(hit, leaf.shape, mesh)
                return hit
        return repl

    return jax.tree_util.tree_map_with_path(assign, shape)


def fused_lm_loss_enabled(engine) -> bool:
    """Whether `engine` wants hidden_loss-tagged (fused vocab-chunked head)
    loss functions — the one probe shared by the SFT engine and PPO actor."""
    cfg = getattr(engine, "config", None)
    return bool(getattr(getattr(cfg, "jax", None), "fused_lm_loss", False))


class DcnWeightPush:
    """Handle for an in-flight staged "dcn" weight push.

    `stage_fn` (bucket streaming, generation live) runs on a daemon thread
    started at construction; the learner keeps training meanwhile. Anything
    `stage_fn` touches must therefore be thread-safe against the main
    thread — RemoteInfEngine guards its sync stats with `_stats_lock` for
    exactly this caller (see docs/architecture.md threading model). The
    caller picks the synchronization point: `commit()` joins the staging
    thread and runs `commit_fn` — the only pause the decode fleet sees.
    A staging error surfaces at join/commit; `abort()` drops server-side
    staging for a push that will never commit. Either field may be None
    (non-streaming ranks of a multi-host learner; legacy single-shot
    transports where commit is a bare join)."""

    def __init__(
        self,
        stage_fn: Callable[[], None] | None,
        commit_fn: Callable[[], None] | None,
        abort_fn: Callable[[], None] | None = None,
    ):
        self._error: BaseException | None = None
        self._commit_fn = commit_fn
        self._abort_fn = abort_fn
        self._t0 = time.monotonic()
        self.stage_secs = 0.0
        self.commit_secs = 0.0
        self.committed = False
        if stage_fn is None:
            self._thread = None
        else:

            def _run():
                try:
                    stage_fn()
                except BaseException as e:  # noqa: BLE001 — raised at join
                    self._error = e
                finally:
                    self.stage_secs = time.monotonic() - self._t0

            self._thread = threading.Thread(
                target=_run, daemon=True, name="dcn-weight-push"
            )
            self._thread.start()

    def join(self, timeout: float | None = None) -> None:
        """Wait for staging to finish; re-raise its error, if any."""
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError("dcn weight push still staging")
        if self._error is not None:
            raise self._error

    def commit(self) -> None:
        """join(), then enter the pause window and commit (idempotent)."""
        if self.committed:
            return
        self.join()
        if self._commit_fn is not None:
            t0 = time.monotonic()
            self._commit_fn()
            self.commit_secs = time.monotonic() - t0
        self.committed = True
        logger.info(
            f"dcn weight push: staged {self.stage_secs:.2f}s (generation "
            f"live) + commit pause {self.commit_secs:.2f}s"
        )

    def abort(self) -> None:
        """Best-effort: drop server-side staging for this push."""
        try:
            self.join()
        except BaseException as e:  # noqa: BLE001 — aborting a failed
            # push is fine; its failure was already raised to the caller
            logger.debug(f"aborting failed push: join raised {e!r}")
        if self._abort_fn is not None and not self.committed:
            self._abort_fn()


class JaxTrainEngine(TrainEngine):
    """GSPMD training engine for decoder LMs (parity: FSDPEngine)."""

    def __init__(self, config: TrainEngineConfig):
        self.config = config
        self.mesh: jax.sharding.Mesh | None = None
        self.parallel_strategy: ParallelStrategy | None = None
        self.model_config: ModelConfig | None = None
        self.params = None
        self.opt_state = None
        self.optimizer = None
        self.lr_schedule = None
        self.ft_spec: FinetuneSpec | None = None
        self._version = 0
        self._step_count = 0
        self._train_mode = True
        self._param_shardings = None
        self._opt_shardings = None
        self._mb_sharding = None
        self._grad_step_cache: dict[int, Callable] = {}
        # (micro-batch tokens, fused head) -> (sets of `hbm.REMAT_SETS` a grad
        # step of that shape keeps, their bytes a chip): see _remat_kept
        self._remat_choice: dict[tuple[int, bool], tuple[int, int]] = {}
        self._fwd_cache: dict[int, Callable] = {}
        # (program, jitted fn, shape key) dispatched at least once: see _run
        self._programs_seen: set[tuple] = set()
        self._apply_update_fn = None
        self._zero_grads_fn = None
        self._push_cast_fn = None
        self._push_quant_fn = None
        self._push_quant_fn = None  # int8 weight-serving push (ISSUE 16)
        self._ocp_checkpointer = None
        self.rollout_engine: InferenceEngine | None = None
        self.weight_update_meta: WeightUpdateMeta | None = None

    # -- lifecycle ------------------------------------------------------
    def create_process_group(
        self, parallel_strategy: ParallelStrategy | None = None
    ) -> None:
        if parallel_strategy is None:
            parallel_strategy = ParallelStrategy(
                data_parallel_size=jax.device_count()
            )
        if (
            int(os.environ.get("AREAL_TPU_NUM_PROCESSES", "1")) > 1
            and jax.process_count() == 1
        ):  # pragma: no cover - multi-host only
            jax.distributed.initialize()
        from areal_tpu.platforms import enable_compilation_cache

        enable_compilation_cache()
        self.parallel_strategy = parallel_strategy
        num_slices = int(getattr(self.config.jax, "mesh_num_slices", 1))
        if num_slices > 1:
            self.mesh = mesh_lib.build_hybrid_mesh(
                parallel_strategy,
                num_slices=num_slices,
                dcn_axes=tuple(
                    getattr(self.config.jax, "mesh_dcn_axes", None)
                    or (mesh_lib.AXIS_PP,)
                ),
            )
        else:
            self.mesh = mesh_lib.build_mesh(parallel_strategy)
        mesh_lib.set_current_mesh(self.mesh)
        logger.info(
            f"mesh built: {dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
            f"on {self.mesh.devices.flatten().tolist()}"
        )

    def initialize(
        self, addr: str | None = None, ft_spec: FinetuneSpec | None = None
    ) -> None:
        assert self.mesh is not None, "call create_process_group first"
        cfg = self.config
        self.ft_spec = ft_spec
        if self.model_config is None:
            # config speaks "pallas"/"xla" (kernel choice); the model speaks
            # "flash"/"dense" (algorithm). Same axis, different vocabulary.
            attn_impl = {"pallas": "flash", "xla": "dense"}.get(
                cfg.attn_impl, cfg.attn_impl
            )
            overrides: dict[str, Any] = dict(
                dtype=cfg.dtype,
                param_dtype=cfg.dtype,
                remat=cfg.gradient_checkpointing,
                scan_layers=cfg.jax.scan_layers,
                is_critic=cfg.is_critic,
                attn_impl=attn_impl,
                cp_zigzag=cfg.jax.cp_zigzag,
            )
            if cfg.use_lora:
                if not cfg.jax.scan_layers:
                    # the non-scan forward never applies adapters; with the
                    # base frozen, training would silently be a no-op
                    raise ValueError(
                        "use_lora requires jax.scan_layers=True"
                    )
                overrides.update(
                    lora_rank=cfg.lora_rank,
                    lora_alpha=float(cfg.lora_alpha),
                    lora_targets=tuple(cfg.target_modules)
                    or ("q_proj", "v_proj"),
                )
            self.model_config = ModelConfig.from_hf_config(cfg.path, **overrides)

        self._build_shardings()

        if cfg.init_from_scratch or not cfg.path:
            host_params = init_params(
                self.model_config, jax.random.PRNGKey(1)
            )
        else:
            host_params = hf_io.load_hf_params(cfg.path, self.model_config)
        if self.model_config.lora_rank:
            # Adapters always start fresh (HF checkpoints carry the base);
            # they are the ONLY trainable subtree — see _trainable_sub.
            host_params["lora"] = init_lora_params(
                self.model_config, jax.random.PRNGKey(2)
            )
        host_params = self._to_engine_layout(host_params)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s),
            host_params,
            self._param_shardings,
        )
        del host_params

        if cfg.optimizer is not None:
            total_steps = ft_spec.total_train_steps if ft_spec else 1000
            self.optimizer, self.lr_schedule = make_optimizer(
                cfg.optimizer, total_steps
            )
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=self._opt_state_shardings(),
            )(self._trainable_sub(self.params))
            self.opt_state = opt_state
        # for the other engines on these chips (a critic, a reference, a
        # colocated decode engine) to plan around: _remat_kept
        hbm.declare_resident(self, self._own_bytes())

    def _build_shardings(self) -> None:
        """Mesh rules → param/micro-batch NamedShardings (shared by real
        initialization and the abstract plan check, so the two can never
        drift on the sharding layout)."""
        pp_enabled = self.mesh.shape.get(mesh_lib.AXIS_PP, 1) > 1
        v = self._virtual_pp
        if pp_enabled:
            assert self.model_config.scan_layers, (
                "pipeline parallelism (pp>1) requires scan_layers=True: the "
                "stacked [L, ...] layer dim is what shards over the pp axis"
            )
            pp = self.mesh.shape[mesh_lib.AXIS_PP]
            assert self.model_config.num_hidden_layers % (pp * v) == 0, (
                f"num_hidden_layers={self.model_config.num_hidden_layers} "
                f"must divide evenly into pp={pp} x virtual_pp_size={v} "
                f"chunks"
            )
        if v > 1:
            schedule = getattr(self.config.jax, "pipeline_schedule", "1f1b")
            if schedule == "1f1b":
                raise ValueError(
                    "virtual_pp_size>1 requires pipeline_schedule="
                    "'1f1b_interleaved' (or 'gpipe'); plain '1f1b' has one "
                    "contiguous stage per rank"
                )
        rules = mesh_lib.default_rules(
            fsdp=bool(self.config.jax.fsdp_axes), pp=pp_enabled
        )
        axes = param_logical_axes(self.model_config)
        if self.model_config.lora_rank:
            axes["lora"] = lora_param_axes(self.model_config)
        self._param_shardings = jax.tree.map(
            lambda a: mesh_lib.named_sharding(self.mesh, a, rules),
            axes,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        self._mb_sharding = mesh_lib.packed_sharding(self.mesh)

    def plan_compile_check(
        self, mb_tokens: int, loss_fn: Callable | None = None
    ) -> dict:
        """AOT-compile the full sharded train step WITHOUT materializing
        parameters: validates that a real-scale plan (full depth, full
        width) builds into an XLA program — catching sharding rule
        mismatches, axis-divisibility errors, and layout problems — on any
        host, before a single parameter byte is allocated.

        The reference has no analogue: its Megatron/FSDP engines only fail
        at real initialization on real GPUs. Under XLA, compilation is
        separable from execution (`jit(...).lower(abstract).compile()`), so
        a laptop CPU can prove the v5p-128 7B program compiles.

        Returns per-program XLA memory-analysis numbers (bytes) alongside
        the closed-form estimate (utils/hbm.py) for cross-checking.
        """
        assert self.mesh is not None, "call create_process_group first"
        assert self.model_config is not None, "set model_config first"
        assert self.params is None, (
            "plan_compile_check replaces engine state with abstract trees; "
            "run it on a fresh engine (before initialize), not a live one"
        )
        cfg = self.config
        model_cfg = self.model_config
        try:
            self._build_shardings()
            abstract = jax.eval_shape(
                lambda: init_params(model_cfg, jax.random.PRNGKey(0))
            )
            if model_cfg.lora_rank:
                abstract["lora"] = jax.eval_shape(
                    lambda: init_lora_params(model_cfg, jax.random.PRNGKey(0))
                )
            abstract = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh
                ),
                abstract,
                self._param_shardings,
            )
            # _opt_state_shardings path-matches against self.params; the
            # abstract tree serves (eval_shape never touches values)
            self.params = abstract
            if self.optimizer is None and cfg.optimizer is not None:
                self.optimizer, self.lr_schedule = make_optimizer(
                    cfg.optimizer, 1000
                )
            if loss_fn is None:
                from areal_tpu.engine.sft.lm_engine import (
                    compute_packed_sft_loss_fused,
                )

                loss_fn = compute_packed_sft_loss_fused

            grad_dtype = jnp.dtype(cfg.grad_reduce_dtype)
            mb = {
                k: jax.ShapeDtypeStruct(
                    (mb_tokens,), jnp.int32, sharding=self._mb_sharding
                )
                for k in (
                    "input_ids",
                    "position_ids",
                    "segment_ids",
                    "loss_mask",
                )
            }
            acc = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, grad_dtype, sharding=sh
                ),
                self._trainable_sub(abstract),
                self._grad_shardings(),
            )
            weight = jax.ShapeDtypeStruct((), jnp.float32)
            kept_sets, _ = self._remat_kept(
                mb_tokens, fused_head=self._wants_hidden(loss_fn)
            )
            grad_compiled = (
                self._get_grad_step(loss_fn).lower(
                    abstract, acc, weight, mb, kept=hbm.REMAT_SETS[kept_sets]
                )
            ).compile()

            report = {"grad_step": _memory_analysis_dict(grad_compiled)}
            if self._pp_size > 1:
                # The schedule actually used at pp>1 (gpipe / 1f1b /
                # interleaved) compiles too — a plan that only proves the
                # plain grad step would miss stash-layout or hybrid-mesh
                # failures in the pipelined program.
                n_mb = 2 * self._pp_size
                stacked_sh = jax.sharding.NamedSharding(
                    self.mesh,
                    jax.sharding.PartitionSpec(
                        None, (mesh_lib.AXIS_DP, mesh_lib.AXIS_SP)
                    ),
                )
                stacked = {
                    k: jax.ShapeDtypeStruct(
                        (n_mb, mb_tokens), jnp.int32, sharding=stacked_sh
                    )
                    for k in (
                        "input_ids",
                        "position_ids",
                        "segment_ids",
                        "loss_mask",
                    )
                }
                weights = jax.ShapeDtypeStruct((n_mb,), jnp.float32)
                pp_compiled = (
                    self._get_pipelined_grad_step(loss_fn).lower(
                        abstract, stacked, weights
                    )
                ).compile()
                report["pipelined_step"] = _memory_analysis_dict(pp_compiled)
            if self.optimizer is not None:
                opt_abstract = jax.eval_shape(
                    self.optimizer.init, self._trainable_sub(abstract)
                )
                opt_abstract = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(
                        s.shape, s.dtype, sharding=sh
                    ),
                    opt_abstract,
                    self._opt_state_shardings(),
                )
                upd_compiled = (
                    self._get_apply_update().lower(
                        self._trainable_sub(abstract),
                        opt_abstract,
                        acc,
                        weight,
                    )
                ).compile()
                report["apply_update"] = _memory_analysis_dict(upd_compiled)
            return report
        finally:
            # plan-check state must not leak into a later real initialize()
            # — even when .compile() raises (surfacing those errors is this
            # function's advertised use)
            self._grad_step_cache.clear()
            self._remat_choice.clear()
            self._apply_update_fn = None
            self.params = None
            self._opt_shardings = None

    @property
    def _lora(self) -> bool:
        return bool(self.model_config and self.model_config.lora_rank)

    def _trainable_sub(self, tree):
        """The subtree gradients/optimizer apply to: the lora adapters when
        LoRA is on (the frozen base rides under stop_gradient in the grad
        step, so XLA never builds base weight gradients), else everything.
        Works on params and on their sharding tree alike."""
        return tree["lora"] if self._lora else tree

    def _merge_trainable(self, params, new_trainable):
        if self._lora:
            return {**params, "lora": new_trainable}
        return new_trainable

    def _export_params(self):
        """Params for save/push: lora deltas folded into the base kernels
        (consumers — HF export, decode engines — serve plain kernels) and
        layers restored to model order (consumers never see the engine's
        interleaved at-rest layout)."""
        if self._lora:
            return self._to_model_layout(
                merge_lora(self.params, self.model_config)
            )
        return self._to_model_layout(self.params)

    @property
    def _zero1(self) -> bool:
        """ZeRO-1 active: dp-shard moments + the optimizer update."""
        return (
            bool(getattr(self.config.jax, "zero1_optimizer", False))
            and self.mesh is not None
            and self.mesh.shape.get(mesh_lib.AXIS_DP, 1) > 1
        )

    def _opt_state_shardings(self):
        """Cached wrapper around the module-level `opt_state_sharding`
        builder (the single source for moment shardings — initialize,
        apply_update, orbax restore and the plan check all resolve here, so
        none can drift into silently re-replicated moments)."""
        if self._opt_shardings is not None:
            return self._opt_shardings
        self._opt_shardings = opt_state_sharding(
            self.optimizer,
            self._trainable_sub(self.params),
            self._trainable_sub(self._param_shardings),
            self.mesh,
            zero1=self._zero1,
        )
        return self._opt_shardings

    def _grad_shardings(self):
        """Output shardings for optimizer-ready gradients: the param
        shardings, dp-extended under ZeRO-1 so the backward's grad psum
        fuses into a reduce-scatter and the update consumes 1/dp per rank."""
        param_sh = self._trainable_sub(self._param_shardings)
        if not self._zero1:
            return param_sh
        return jax.tree.map(
            lambda s, p: zero1_extend_sharding(s, p.shape, self.mesh),
            param_sh,
            self._trainable_sub(self.params),
        )

    def destroy(self):
        self.params = None
        self.opt_state = None
        hbm.declare_resident(self, 0)
        self._opt_shardings = None
        self._grad_step_cache.clear()
        self._remat_choice.clear()
        self._fwd_cache.clear()
        # Compiled programs hold NamedShardings bound to this mesh/optimizer;
        # a re-initialized engine must not reuse them.
        self._apply_update_fn = None
        self._zero_grads_fn = None
        self._push_cast_fn = None
        self._push_quant_fn = None
        # A dead engine must not leave its topology as the process-global
        # ambient mesh: later traces (a differently-sharded decode engine,
        # plain eval forwards) would constrain onto devices their operands
        # don't live on.
        if self.mesh is not None:
            mesh_lib.clear_current_mesh_if(self.mesh)

    # -- topology -------------------------------------------------------
    # `data_parallel_rank/world_size` follow the reference's *usage* (which
    # host loads which dataset shard / runs which rollout slice,
    # examples/.../gsm8k_grpo.py:58-69) — NOT its GPU-rank semantics. Under
    # single-controller SPMD the unit of host-side work is the PROCESS:
    # every process rolls out its own prompt slice, the slices are host-
    # allgathered into one identical global batch on every process
    # (core/dist_rollout.py), and jit consumes that global batch no matter
    # how dp/tp/sp map onto devices. So process identity is the correct
    # shard key even when dp spans devices within one process (no duplicate
    # data — one process drives all its dp shards with one batch) or when
    # tp/sp spans processes (the extra processes contribute extra rollout
    # throughput, then converge on the same global batch). For the *mesh*
    # topology, use `dp_size`/`tp_size`/`sp_size`/`pp_size`.
    @property
    def data_parallel_rank(self) -> int:
        return jax.process_index()

    @property
    def data_parallel_world_size(self) -> int:
        return jax.process_count()

    @property
    def is_data_parallel_head(self) -> bool:
        return jax.process_index() == 0

    @property
    def dp_size(self) -> int:
        return self.mesh.shape.get(mesh_lib.AXIS_DP, 1) if self.mesh else 1

    @property
    def tp_size(self) -> int:
        return self.mesh.shape.get(mesh_lib.AXIS_TP, 1) if self.mesh else 1

    @property
    def sp_size(self) -> int:
        return self.mesh.shape.get(mesh_lib.AXIS_SP, 1) if self.mesh else 1

    @property
    def pp_size(self) -> int:
        return self._pp_size

    # -- mode -----------------------------------------------------------
    def train(self, mode: bool = True):
        self._train_mode = mode
        return self

    # -- versioning -----------------------------------------------------
    def set_version(self, version: int) -> None:
        self._version = version

    def get_version(self) -> int:
        return self._version

    # -- save / load ----------------------------------------------------
    def save(self, meta: SaveLoadMeta) -> None:
        if meta.weight_format == "hf":
            hf_io.save_hf_params(
                self._export_params(), self.model_config, meta.path
            )
            # copy config.json for reload-ability
            if self.config.path and os.path.exists(
                os.path.join(self.config.path, "config.json")
            ):
                import shutil

                shutil.copy(
                    os.path.join(self.config.path, "config.json"),
                    os.path.join(meta.path, "config.json"),
                )
            if meta.tokenizer is not None:
                meta.tokenizer.save_pretrained(meta.path)
            if meta.with_optim:
                self._orbax_save(
                    os.path.join(meta.path, "optim"),
                    with_params=False,
                    with_optim=True,
                )
        elif meta.weight_format == "orbax":
            self._orbax_save(
                meta.path, with_params=True, with_optim=meta.with_optim
            )
            if meta.tokenizer is not None:
                meta.tokenizer.save_pretrained(meta.path)
        else:
            raise NotImplementedError(meta.weight_format)

    def load(self, meta: SaveLoadMeta) -> None:
        if meta.weight_format == "orbax" or os.path.isdir(
            os.path.join(meta.path, "orbax_state")
        ):
            self._orbax_restore(
                meta.path, with_params=True, with_optim=meta.with_optim
            )
            return
        host_params = hf_io.load_hf_params(meta.path, self.model_config)
        if self._lora:
            # HF checkpoints carry merged kernels (save/_export_params
            # folds the deltas in), so adapters restart at zero-delta —
            # keeping the trained A,B would double-apply the delta on top
            # of a base that already contains it.
            host_params["lora"] = init_lora_params(
                self.model_config, jax.random.PRNGKey(2)
            )
        host_params = self._to_engine_layout(host_params)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s),
            host_params,
            self._param_shardings,
        )
        optim_dir = os.path.join(meta.path, "optim")
        if meta.with_optim and os.path.isdir(optim_dir):
            self._orbax_restore(optim_dir, with_params=False, with_optim=True)

    # Sharded checkpointing via orbax (parity: the reference's "dcp" recover
    # format, areal/utils/recover.py:139-332 + megatron_checkpointer). Each
    # process writes only its own shards — no host gather of a ~70 GB
    # optimizer tree at 7B+AdamW, unlike the round-1/2 pickle+npz path this
    # replaces.
    def _checkpointer(self):
        if self._ocp_checkpointer is None:
            import orbax.checkpoint as ocp

            self._ocp_checkpointer = ocp.StandardCheckpointer()
        return self._ocp_checkpointer

    def _ckpt_state(self, with_params: bool, with_optim: bool) -> dict:
        state = {}
        if with_params:
            state["params"] = self.params
        if with_optim and self.opt_state is not None:
            state["opt_state"] = self.opt_state
        return state

    def _orbax_save(
        self, path: str, *, with_params: bool, with_optim: bool
    ) -> None:
        import json as _json

        ckptr = self._checkpointer()
        state = self._ckpt_state(with_params, with_optim)
        ckptr.save(
            os.path.join(os.path.abspath(path), "orbax_state"),
            state,
            force=True,
        )
        # Block until durable: recover markers must not precede the data.
        ckptr.wait_until_finished()
        if jax.process_index() == 0:
            with open(os.path.join(path, "train_meta.json"), "w") as f:
                _json.dump(
                    dict(
                        step_count=self._step_count,
                        version=self._version,
                        layer_layout=self._layer_layout_tag(),
                    ),
                    f,
                )

    def _orbax_restore(
        self, path: str, *, with_params: bool, with_optim: bool
    ) -> None:
        import json as _json

        ckptr = self._checkpointer()
        meta_path = os.path.join(path, "train_meta.json")
        if with_params and os.path.exists(meta_path):
            with open(meta_path) as f:
                stored = _json.load(f).get("layer_layout", "model")
            if stored != self._layer_layout_tag():
                # orbax trees are restored positionally — loading a
                # model-order checkpoint into an interleaved engine (or
                # vice versa, or a different pp×v) would silently scramble
                # the layer stack
                raise ValueError(
                    f"checkpoint layer layout {stored!r} does not match the "
                    f"engine's {self._layer_layout_tag()!r} (pipeline_"
                    f"schedule/virtual_pp_size changed since the save?)"
                )
        state = self._ckpt_state(with_params, with_optim)
        shardings = {}
        if with_params:
            shardings["params"] = self._param_shardings
        if with_optim and self.opt_state is not None:
            shardings["opt_state"] = self._opt_state_shardings()
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state,
            shardings,
        )
        restored = ckptr.restore(
            os.path.join(os.path.abspath(path), "orbax_state"), abstract
        )
        if with_params:
            self.params = restored["params"]
        if "opt_state" in restored:
            self.opt_state = restored["opt_state"]
        meta_path = os.path.join(path, "train_meta.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                m = _json.load(f)
            self._step_count = m["step_count"]
            self._version = m["version"]

    # -- weight updates -------------------------------------------------
    def connect_engine(self, engine: InferenceEngine, meta: WeightUpdateMeta):
        self.rollout_engine = engine
        self.weight_update_meta = meta
        engine.init_weights_update_group(meta)
        return self

    def update_weights(self, meta: WeightUpdateMeta | None = None) -> None:
        from areal_tpu.core import fault_injection

        meta = meta or self.weight_update_meta
        assert meta is not None
        # chaos seam: trainer death mid weight-push — decode servers keep
        # the old version, the restored trainer re-pushes after load
        fault_injection.fire("train.weights.push", version=self.get_version())
        if meta.type == "memory":
            # Colocated fast path: hand the sharded jax.Arrays directly to
            # the decode engine, which device_puts onto its own shardings —
            # the TPU analogue of the reference NCCL broadcast
            # (fsdp_engine.py:298-401).
            assert self.rollout_engine is not None
            with perf_tracer.span("weights/stage", version=self.get_version()):
                params = self._export_params()
            self.rollout_engine.update_weights_from_distributed(
                meta, params, self.model_config
            )
        elif meta.type == "disk":
            start = time.monotonic()
            hf_io.save_hf_params(
                self._export_params(), self.model_config, meta.path
            )
            # name_resolve timestamp handshake (fsdp_engine.py:403-425)
            update_name = names.update_weights_from_disk(
                self.config.experiment_name,
                self.config.trial_name,
                self.get_version(),
            )
            name_resolve.add(
                update_name, str(time.time_ns()), replace=True
            )
            if self.rollout_engine is not None:
                self.rollout_engine.update_weights_from_disk(meta)
            logger.info(
                f"disk weight update took {time.monotonic() - start:.2f}s"
            )
        elif meta.type == "dcn":
            # In-memory network push — staged: see update_weights_async.
            # The synchronous entry stages and commits back-to-back; the
            # decode fleet still generates through the whole bucket
            # transfer and only pauses for the commit/apply.
            self.update_weights_async(meta).commit()
        else:
            raise NotImplementedError(f"weight update type {meta.type}")

    def _dcn_payload(self, inflight: int, weight_dtype: str = "fp"):
        """(named, lora_scale) for a dcn push.

        weight_dtype="int8" (WeightUpdateMeta.weight_dtype) quantizes the
        dense matmul kernels ONCE, here at the producer, AFTER the bf16
        push cast — the int8 grid then snapshots exactly the bf16 values
        the fp wire would have shipped, so consumer drift vs the fp oracle
        measures quantization error alone. Each kernel becomes a
        {"q" int8, "scale" f32} subtree whose leaves flatten to the
        `.../q` + `.../scale` wire names; wire bytes drop ~2x (int8 data
        vs bf16, scales are one f32 per output channel). The trainer's
        fp32 master weights are untouched. LoRA delta pushes stay fp: the
        `lora/...` subtree has no quantizable kernels, so the quantize
        pass is a no-op on it by construction.

        Under LoRA (+ weight_sync_delta) only the trainable adapter
        subtree goes on the wire (`lora/...` names; servers fold
        base + scale·A@B at commit) — orders of magnitude fewer bytes than
        the merged full tree. Otherwise the full (merged) tree is pushed.

        On a multi-host learner params are fsdp-sharded across processes,
        so the gather is a *collective*: every process participates in
        process_allgather (ICI/DCN all-gather under jit) and only process 0
        streams. Single-host, the result is a LAZY (name, array) producer:
        device→host copies of the next `inflight` tensors run asynchronously
        while earlier buckets are packed and POSTed (one batched transfer
        per tensor via copy_to_host_async instead of the old per-leaf
        serial jax.device_get tree_map)."""
        from areal_tpu.core.weight_transfer import (
            flatten_named,
            iter_prefetched,
            named_leaves,
        )

        if self._push_cast_fn is None:
            self._push_cast_fn = jax.jit(
                lambda t: jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else x,
                    t,
                )
            )
        delta = self._lora and getattr(self.config, "weight_sync_delta", True)
        if delta:
            # adapters go on the wire in MODEL layer order — decode servers
            # fold base + scale·A@B by model layer index
            casted = self._push_cast_fn(
                self._to_model_layout({"lora": self.params["lora"]})
            )
            lora_scale = self.model_config.lora_alpha / max(
                self.model_config.lora_rank, 1
            )
        else:
            casted = self._push_cast_fn(self._export_params())
            lora_scale = None
        if weight_dtype == "int8":
            if self._push_quant_fn is None:
                from areal_tpu.models.qwen2 import quantize_weights

                self._push_quant_fn = jax.jit(quantize_weights)
            casted = self._push_quant_fn(casted)
        elif weight_dtype != "fp":
            from areal_tpu.models.qwen2 import WEIGHT_DTYPES

            raise ValueError(
                f"weight_dtype={weight_dtype!r} not in {WEIGHT_DTYPES}"
            )
        if jax.process_count() > 1:  # pragma: no cover - multi-host only
            from jax.experimental import multihost_utils

            host = multihost_utils.process_allgather(casted, tiled=True)
            return flatten_named(host), lora_scale
        return (
            iter_prefetched(named_leaves(casted), window=max(inflight, 2)),
            lora_scale,
        )

    def update_weights_async(
        self, meta: WeightUpdateMeta | None = None
    ) -> "DcnWeightPush":
        """Start a dcn weight push WITHOUT blocking the train loop: the
        stage phase (host gather + bucket streaming, generation live) runs
        on a background thread, so the learner can enter its next
        train_batch while buckets drain. Call `.commit()` on the returned
        handle at the chosen synchronization point — it joins the staging
        thread, then pauses the decode fleet only for the commit/apply.

        Safe against donation: the on-device bf16 cast (`_push_cast_fn`)
        runs synchronously here, producing buffers the optimizer never
        donates — the staging thread reads those copies, not live params,
        so the next train_batch may mutate/donate `self.params` freely.
        On multi-host learners the allgather collective also runs
        synchronously (every process must participate); only the HTTP
        streaming is backgrounded, on process 0."""
        meta = meta or self.weight_update_meta
        assert meta is not None and meta.type == "dcn", (
            "update_weights_async supports the staged 'dcn' transport; use "
            "update_weights for disk/memory"
        )
        engine = self.rollout_engine
        assert engine is not None, "connect_engine first"
        inflight = getattr(
            getattr(engine, "config", None), "weight_sync_inflight_buckets", 2
        )
        chunk_mb = getattr(meta, "weight_chunked_mem_mb", None) or 512
        named, lora_scale = self._dcn_payload(
            inflight, getattr(meta, "weight_dtype", "fp")
        )
        version = self.get_version()
        if jax.process_index() != 0:  # pragma: no cover - multi-host only
            return DcnWeightPush(None, None)  # collective already done
        staged_api = hasattr(engine, "stage_weights") and hasattr(
            engine, "commit_staged"
        )
        if not staged_api:
            # legacy/stub engines: whole push on the background thread
            if not hasattr(named, "items"):
                from areal_tpu.core.weight_transfer import flatten_named

                named = dict(named)
            return DcnWeightPush(
                lambda: engine.update_weights_from_tensor(
                    named, version=version, chunk_mb=chunk_mb
                ),
                None,
            )
        push_id = engine._new_push_id() if hasattr(
            engine, "_new_push_id"
        ) else f"push-{version}"

        def _stage():
            with perf_tracer.span("weights/stage", version=version):
                engine.stage_weights(
                    named, push_id=push_id, chunk_mb=chunk_mb,
                    inflight=inflight,
                )

        def _commit():
            with perf_tracer.span("weights/commit", version=version):
                engine.commit_staged(
                    push_id, version=version, lora_scale=lora_scale
                )

        def _abort():
            engine.abort_push(push_id)

        return DcnWeightPush(_stage, _commit, _abort)

    # -- compute --------------------------------------------------------
    def _host_mb(self, mb: dict[str, Any]) -> dict[str, np.ndarray]:
        """Select token-aligned arrays, add position/segment ids (host)."""
        cu = mb["cu_seqlens"]
        total = int(cu[-1])
        out: dict[str, Any] = {}
        for k, v in mb.items():
            if k in ("cu_seqlens", "max_seqlen"):
                continue
            if isinstance(v, np.ndarray) and v.ndim >= 1 and v.shape[0] == total:
                out[k] = v
        seg = segment_ids_from_cu_seqlens(np.asarray(cu), total)
        pos = np.arange(total, dtype=np.int32) - np.repeat(
            np.asarray(cu[:-1]), np.diff(np.asarray(cu))
        ).astype(np.int32)
        if (
            self.model_config is not None
            and self.model_config.pos_embed == "learned"
            and pos.size
            and int(pos.max()) >= self.model_config.max_position_embeddings
        ):
            # jax gathers clamp out-of-bounds indices, so an overlong
            # sequence would silently reuse the last wpe row where HF
            # raises an index error — fail loudly instead.
            raise ValueError(
                f"sequence position {int(pos.max())} exceeds the learned "
                "position table "
                f"(max_position_embeddings="
                f"{self.model_config.max_position_embeddings})"
            )
        out["segment_ids"] = seg
        out["position_ids"] = pos
        return out

    def _attn_block_pcts(self, mbs: list[dict[str, Any]]) -> tuple[float, float]:
        """(live, walked) block pairs as percentages of all block pairs of
        these micro-batches, by the flash kernels' own rule and work list on
        the host's segment ids (NumPy; no device read), in the layout the
        model gives the kernels: the packed row whole, or in ring shards,
        zig-zag permuted where the model permutes. Walked: the inner steps
        `%flash_fwd`, `%flash_dq` and `%flash_dkv` take over their three
        grids; above live where a run of the work list has a hole."""
        cfg = self.model_config
        ring = cfg is not None and resolve_attn_impl(cfg) == "ring"
        live = walked = visits = 0
        for mb in mbs:
            cu = np.asarray(mb["cu_seqlens"])
            total = int(cu[-1])
            seg = segment_ids_from_cu_seqlens(cu, total)
            pos = np.arange(total, dtype=np.int32)
            n = cp_ring_shards(total, self.mesh) if ring else 0
            if n and cfg.cp_zigzag and zigzag_eligible(total, self.mesh):
                perm = zigzag_indices(total, n)
                seg, pos = seg[perm], pos[perm]
            a, (walk_q, walk_k), b = live_block_counts(seg, pos, total // max(n, 1))
            live, walked, visits = live + a, walked + 2 * walk_q + walk_k, visits + b
        visits = max(visits, 1)
        return 100.0 * live / visits, 100.0 * walked / (3 * visits)

    def _device_mb(self, mb: dict[str, Any]) -> dict[str, jax.Array]:
        """One packed micro-batch on device with the token sharding."""
        return {
            k: jax.device_put(jnp.asarray(v), self._mb_sharding)
            for k, v in self._host_mb(mb).items()
        }

    # -- pipelined compute (pp > 1) -------------------------------------
    @property
    def _pp_size(self) -> int:
        return self.mesh.shape.get(mesh_lib.AXIS_PP, 1) if self.mesh else 1

    @property
    def _virtual_pp(self) -> int:
        return max(int(getattr(self.config.jax, "virtual_pp_size", 1) or 1), 1)

    def _layer_perm(self) -> list[int] | None:
        """Chunk-major interleaved storage permutation for the scanned layer
        stack, or None when the engine stores layers in model order (no
        virtual stages). With v>1 the engine keeps `layers` (and `lora`)
        PERMUTED at rest so the schedule's [L]→[pp,v,Lc] reshape is pure
        metadata — the same permute-at-entry pattern as cp_zigzag."""
        v = self._virtual_pp
        if v <= 1 or self._pp_size <= 1:
            return None
        from areal_tpu.parallel.pipeline import interleave_layer_indices

        return interleave_layer_indices(
            self.model_config.num_hidden_layers, self._pp_size, v
        )

    def _layer_layout_tag(self) -> str:
        """Checkpoint guard string for the at-rest layer order."""
        if self._layer_perm() is None:
            return "model"
        return f"interleaved-pp{self._pp_size}-v{self._virtual_pp}"

    def _to_engine_layout(self, host_params):
        """Model layer order → the engine's at-rest (chunk-major) order;
        identity when no interleaving is active."""
        perm = self._layer_perm()
        if perm is None:
            return host_params
        idx = np.asarray(perm)
        out = dict(host_params)
        for k in ("layers", "lora"):
            if k in out:
                out[k] = jax.tree.map(lambda x: x[idx], out[k])
        return out

    def _to_model_layout(self, params):
        """Engine at-rest order → model layer order (export/save/push)."""
        perm = self._layer_perm()
        if perm is None:
            return params
        from areal_tpu.parallel.pipeline import (
            inverse_interleave_layer_indices,
        )

        inv = jnp.asarray(
            inverse_interleave_layer_indices(
                self.model_config.num_hidden_layers,
                self._pp_size,
                self._virtual_pp,
            )
        )
        out = dict(params)
        for k in ("layers", "lora"):
            if k in out:
                out[k] = jax.tree.map(lambda x: jnp.take(x, inv, axis=0), out[k])
        return out

    def _stack_mbs(self, mbs: list[dict[str, Any]]) -> dict[str, jax.Array]:
        """Pad every packed micro-batch to a common bucket and stack into
        [M, T] device arrays — the microbatch stream of the pipeline.

        The stacked shape (M, T) keys the jit cache: T is already bucketed
        to 128s; M is the FFD bin count, which is stable for a fixed token
        budget. A step with an unusual M pays one extra compile.
        """
        from areal_tpu.utils.data import pad_packed_tensor_dict

        t_max = max(int(mb["cu_seqlens"][-1]) for mb in mbs)
        hosts = []
        for mb in mbs:
            if int(mb["cu_seqlens"][-1]) < t_max:
                mb, _ = pad_packed_tensor_dict(mb, pad_to_length=t_max)
            hosts.append(self._host_mb(mb))
        sharding = jax.sharding.NamedSharding(
            self.mesh,
            jax.sharding.PartitionSpec(
                None, (mesh_lib.AXIS_DP, mesh_lib.AXIS_SP)
            ),
        )
        keys = set(hosts[0])
        for h in hosts[1:]:
            keys &= set(h)
        return {
            k: jax.device_put(
                jnp.asarray(np.stack([h[k] for h in hosts])), sharding
            )
            for k in keys
        }

    @staticmethod
    def _returns_aux(fn: Callable | None) -> bool:
        """Loss functions tagged `returns_aux=True` return (loss, aux) where
        aux is a dict of scalar training statistics (entropy, clip ratios,
        KL terms). The engine weight-averages aux across micro-batches into
        the train_batch stats — the reference records the same per-update
        stats from inside its loss (areal/engine/ppo/actor.py:335-377)."""
        return bool(getattr(fn, "returns_aux", False))

    @staticmethod
    def _wants_hidden(fn: Callable | None) -> bool:
        """Loss/hook functions tagged `hidden_loss=True` consume an LMHead
        (vocab-chunked fused head, ops/fused_xent.py) instead of dense
        [T, V] logits — the TPU answer to the reference's Megatron
        vocab-parallel cross-entropy."""
        return bool(getattr(fn, "hidden_loss", False))

    def _get_pipelined_grad_step(self, loss_fn: Callable) -> Callable:
        """One jitted program running ALL micro-batches through the pp
        stages (fill/steady/drain) with ONE optimizer-ready gradient.
        Replaces the per-mb grad-accumulation loop when pp > 1 (the python
        loop would leave every stage idle (pp-1)/pp of the time).

        `jax.pipeline_schedule` picks the schedule:
        - "1f1b" (default): parallel/pipeline.pipeline_1f1b_grads — each
          microbatch's backward is interleaved right behind its forward, so
          the live activation stash is capped at 2·pp-1 stage inputs
          instead of growing with M; bigger M (smaller bubble) fits in
          fixed HBM.
        - "1f1b_interleaved": same memory discipline, but each rank runs
          `virtual_pp_size` non-contiguous virtual stages
          (pipeline_1f1b_interleaved_grads) — bubble shrinks ~1/v, stash
          bound v·(2·pp-1); grads bitwise-equal to "1f1b".
        - "gpipe": the all-forward-then-all-backward reference path
          (autodiff through the trunk scan); numerically the oracle the
          1f1b paths are tested against.
        """
        schedule = getattr(self.config.jax, "pipeline_schedule", "1f1b")
        from areal_tpu.parallel.pipeline import PIPELINE_SCHEDULES

        if schedule not in PIPELINE_SCHEDULES:
            raise ValueError(
                f"jax.pipeline_schedule={schedule!r} not in "
                f"{PIPELINE_SCHEDULES}"
            )
        virtual = self._virtual_pp
        if virtual > 1 and schedule == "1f1b":
            raise ValueError(
                "virtual_pp_size>1 requires pipeline_schedule="
                "'1f1b_interleaved' (or 'gpipe')"
            )
        key = ("pp", schedule, virtual, id(loss_fn))
        if key in self._grad_step_cache:
            return self._grad_step_cache[key]
        from areal_tpu.models.qwen2 import forward_pipelined

        model_cfg = self.model_config
        mesh = self.mesh
        grad_sh = self._grad_shardings()
        use_aux = bool(
            model_cfg.num_experts and model_cfg.router_aux_loss_coef > 0
        )

        hidden_mode = self._wants_hidden(loss_fn)
        aux_mode = self._returns_aux(loss_fn)
        lora_mode = self._lora

        if schedule in ("1f1b", "1f1b_interleaved"):
            from areal_tpu.models.qwen2 import forward_pipelined_grads

            if aux_mode:
                per_mb = lambda out, mb: loss_fn(out, mb)  # noqa: E731
            else:
                per_mb = lambda out, mb: (loss_fn(out, mb), {})  # noqa: E731

            vpp = virtual if schedule == "1f1b_interleaved" else 1

            def pip_1f1b_step(params, stacked, weights):
                if lora_mode:
                    trainable = params["lora"]
                    frozen = {k: v for k, v in params.items() if k != "lora"}
                else:
                    trainable, frozen = params, {}
                losses, stats, _aux_total, grads = forward_pipelined_grads(
                    trainable,
                    frozen,
                    stacked["input_ids"],
                    stacked["position_ids"],
                    stacked["segment_ids"],
                    model_cfg,
                    mesh,
                    per_mb,
                    stacked,
                    weights,
                    head_mode="hidden" if hidden_mode else "logits",
                    lora_mode=lora_mode,
                    virtual_pp=vpp,
                )
                grads = jax.lax.with_sharding_constraint(grads, grad_sh)
                return losses, stats, grads

            fn = jax.jit(
                pip_1f1b_step,
                out_shardings=(
                    mesh_lib.replicated(self.mesh),
                    mesh_lib.replicated(self.mesh),
                    grad_sh,
                ),
            )
            self._grad_step_cache[key] = fn
            return fn

        def loss_of(trainable, frozen, stacked, weights):
            params = (
                {**frozen, "lora": trainable} if lora_mode else trainable
            )
            if hidden_mode:
                per_mb_fn = lambda h, mb: loss_fn(  # noqa: E731
                    LMHead(h, params, model_cfg), mb
                )
            else:
                per_mb_fn = lambda logits, mb: loss_fn(logits, mb)  # noqa: E731
            out = forward_pipelined(
                params,
                stacked["input_ids"],
                stacked["position_ids"],
                stacked["segment_ids"],
                model_cfg,
                mesh,
                per_mb_fn=per_mb_fn,
                mb_data=stacked,
                with_aux=use_aux,
                head_mode="hidden" if hidden_mode else "logits",
                virtual_pp=virtual,
            )
            per_mb, aux = out if use_aux else (out, jnp.float32(0.0))
            if aux_mode:
                losses, stats = per_mb  # ([M], {k: [M]})
            else:
                losses, stats = per_mb, {}
            total = jnp.sum(losses * weights)
            if use_aux:
                total = total + model_cfg.router_aux_loss_coef * aux
            return total, (losses, stats)

        def pip_grad_step(params, stacked, weights):
            if lora_mode:
                trainable = params["lora"]
                frozen = jax.lax.stop_gradient(
                    {k: v for k, v in params.items() if k != "lora"}
                )
            else:
                trainable, frozen = params, {}
            (_, (losses, stats)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(trainable, frozen, stacked, weights)
            grads = jax.lax.with_sharding_constraint(grads, grad_sh)
            return losses, stats, grads

        fn = jax.jit(
            pip_grad_step,
            out_shardings=(
                mesh_lib.replicated(self.mesh),
                mesh_lib.replicated(self.mesh),
                grad_sh,
            ),
        )
        self._grad_step_cache[key] = fn
        return fn

    def _get_grad_step(self, loss_fn: Callable) -> Callable:
        key = id(loss_fn)
        if key in self._grad_step_cache:
            return self._grad_step_cache[key]
        model_cfg = self.model_config
        grad_dtype = jnp.dtype(self.config.grad_reduce_dtype)

        hidden_mode = self._wants_hidden(loss_fn)
        aux_mode = self._returns_aux(loss_fn)
        lora_mode = self._lora

        def loss_of(trainable, frozen, mb, *, kept):
            params = (
                {**frozen, "lora": trainable} if lora_mode else trainable
            )
            # engine-layout (interleaved) layer storage → model order for
            # the plain forward; differentiating through the gather puts
            # the grads back into engine layout automatically
            params = self._to_model_layout(params)
            with_aux = bool(
                model_cfg.num_experts and model_cfg.router_aux_loss_coef > 0
            )
            out = model_forward(
                params,
                mb["input_ids"],
                mb["position_ids"],
                mb["segment_ids"],
                model_cfg,
                with_aux=with_aux,
                return_hidden=hidden_mode,
                remat_kept=kept,
            )
            x, aux = out if with_aux else (out, None)
            if hidden_mode:
                x = LMHead(x, params, model_cfg)
            with jax.named_scope("loss"):
                res = loss_fn(x, mb)
            loss, stats = res if aux_mode else (res, {})
            if with_aux:
                loss = loss + model_cfg.router_aux_loss_coef * aux
            return loss, stats

        grad_sh = self._grad_shardings()

        def grad_step(params, acc, weight, mb, kept=()):
            # `kept` (static): the named intermediates each layer's backward
            # keeps, decided a shape before it is traced (_run_grad_step)
            if lora_mode:
                trainable = params["lora"]
                frozen = jax.lax.stop_gradient(
                    {k: v for k, v in params.items() if k != "lora"}
                )
            else:
                trainable, frozen = params, {}
            (loss, stats), grads = jax.value_and_grad(
                functools.partial(loss_of, kept=kept), has_aux=True
            )(trainable, frozen, mb)
            # Pin gradients to their parameter's layout BEFORE accumulation:
            # left free, XLA may lay the backward's psum outputs out
            # differently from the donated accumulator and fall back to
            # "involuntary full rematerialization" reshards on every step.
            grads = jax.lax.with_sharding_constraint(grads, grad_sh)
            with jax.named_scope("grad_accum"):
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(grad_dtype) * weight, acc, grads
                )
            return loss, stats, acc

        fn = jax.jit(
            grad_step,
            donate_argnums=(1,),
            static_argnames=("kept",),
            out_shardings=(
                mesh_lib.replicated(self.mesh),
                mesh_lib.replicated(self.mesh),
                grad_sh,
            ),
        )
        self._grad_step_cache[key] = fn
        return fn

    def _own_bytes(self) -> int:
        """Bytes a chip holds of this engine from step to step: parameters,
        optimizer state and the gradient accumulator, each as sharded (of
        real or abstract arrays). Arithmetic on shapes and shardings: the
        same in every run and on every process of a multi-host mesh."""
        leaves = jax.tree.leaves
        trainable = self._trainable_sub(self.params)
        total = hbm.sharded_bytes(leaves(self.params))
        if self.optimizer is None:  # a reference: no accumulator, no state
            return total
        total += hbm.sharded_bytes(
            leaves(trainable), leaves(self._grad_shardings()),
            self.config.grad_reduce_dtype,
        )
        if self.opt_state is not None:
            return total + hbm.sharded_bytes(leaves(self.opt_state))
        return total + hbm.sharded_bytes(  # a plan check: abstract state
            leaves(jax.eval_shape(self.optimizer.init, trainable)),
            leaves(self._opt_state_shardings()),
        )

    def _live_bytes(self) -> int | None:
        """What the backend says is in use on one of this process's chips of
        the mesh; None where it says nothing (the CPU). Read to CHECK the
        account `_remat_kept` plans from, never to plan: it moves with the
        allocator from run to run, differs between hosts, and a device of
        another process cannot be asked at all."""
        here = jax.process_index()
        for dev in self.mesh.devices.flat:
            if dev.process_index == here:
                stats = dev.memory_stats() or {}
                return stats.get("bytes_in_use")
        return None

    def _kept_shape(self, tokens: int) -> dict:
        """What `hbm.remat_kept_bytes` needs of a micro-batch of `tokens` on
        this mesh: its tokens a chip, the ring's steps, the tp degree."""
        shape = self.mesh.shape
        ring = resolve_attn_impl(self.model_config) == "ring"
        return dict(
            tokens=max(1, tokens // (
                shape.get(mesh_lib.AXIS_DP, 1) * shape.get(mesh_lib.AXIS_SP, 1)
            )),
            ring_steps=max(cp_ring_shards(tokens, self.mesh), 1) if ring else 1,
            tp=shape.get(mesh_lib.AXIS_TP, 1),
        )

    def _remat_kept(self, tokens: int, *, fused_head: bool = True) -> tuple[int, int]:
        """(how many of `hbm.REMAT_SETS` a grad step over `tokens` packed
        tokens keeps of each layer in place of recomputing them, their bytes
        a chip): the largest set that fits what the chip has left beside
        what is resident (this engine's state by `_own_bytes`, the other
        engines' as they declared it: `hbm.declare_resident`) and the
        full-recompute step (`hbm.choose_remat_kept`). Arithmetic on the
        shape, the mesh and the chip's kind, made before the shape's program
        is traced: no compile, the same answer in every run and on every
        process. Decided once a shape and remembered (`_run_grad_step` lowers
        it if the chip refuses the program). A chip of unknown capacity and
        a model without `gradient_checkpointing` keep nothing."""
        cfg = self.model_config
        if not cfg.remat:
            return 0, 0
        if (tokens, fused_head) not in self._remat_choice:
            shape = self.mesh.shape
            est = hbm.estimate_train_hbm(
                cfg,
                dp=shape.get(mesh_lib.AXIS_DP, 1),
                tp=shape.get(mesh_lib.AXIS_TP, 1),
                sp=shape.get(mesh_lib.AXIS_SP, 1),
                microbatch_tokens=tokens,
                fused_lm_head=fused_head,
            )
            try:
                capacity = hbm.hbm_bytes(self.mesh.devices.flat[0].device_kind)
            except ValueError:  # no capacity known for this device
                capacity = 0
            resident = self._own_bytes() + hbm.declared_resident_bytes(but=self)
            room = capacity and hbm.train_room_bytes(
                capacity, resident,
                est.activation_bytes + est.logits_bytes + est.grad_transient_bytes,
            )
            at = self._kept_shape(tokens)
            n, kept = hbm.choose_remat_kept(cfg, room_bytes=room, **at)
            self._remat_choice[tokens, fused_head] = (n, kept)
            logger.info(
                f"grad_step T={at['tokens']}/chip: keeping "
                f"{' + '.join(('attention', 'mlp')[:n]) or 'nothing'}, "
                f"{kept / 1e9:.2f} GB of {max(room, 0) / 1e9:.2f} GB room"
            )
            live = self._live_bytes() if n else None
            if live is not None and live - resident > capacity // 16:
                logger.warning(
                    f"the chip has {live / 1e9:.2f} GB in use where this "
                    f"engine and what others declared come to "
                    f"{resident / 1e9:.2f} GB: something on it is not in the "
                    "account (hbm.declare_resident) the kept set was chosen from"
                )
        return self._remat_choice[tokens, fused_head]

    def _run_grad_step(self, grad_step, fused_head: bool, tokens: int, acc, *args):
        """Dispatch `grad_step` for a micro-batch of `tokens`, keeping what
        `_remat_kept` chose for the shape. If the chip refuses for memory a
        program that has never run (XLA's plan does not fit, or its first
        execution finds no room: RESOURCE_EXHAUSTED) and the accumulator was
        not consumed, the shape's choice is lowered by one set for good, the
        log says so, and the same call is made again: at nothing kept the
        program is full recompute's, and its error is raised as it came."""
        while True:
            n, _ = self._remat_kept(tokens, fused_head=fused_head)
            first = ("grad_step", id(grad_step), tokens) not in self._programs_seen
            try:
                return self._run(
                    "grad_step", grad_step, tokens, self.params, acc, *args,
                    kept=hbm.REMAT_SETS[n],
                )
            except jax.errors.JaxRuntimeError as e:
                consumed = any(x.is_deleted() for x in jax.tree.leaves(acc))
                if not (first and n and "RESOURCE_EXHAUSTED" in str(e)) or consumed:
                    raise
                at = self._kept_shape(tokens)
                self._remat_choice[tokens, fused_head] = (
                    n - 1, hbm.remat_kept_bytes(self.model_config, n_sets=n - 1, **at)
                )
                logger.warning(
                    f"grad_step T={at['tokens']}/chip: the chip refused the program "
                    f"that keeps {n} of {len(hbm.REMAT_SETS) - 1} sets "
                    f"({str(e).splitlines()[0][:200]}); keeping {n - 1}"
                )

    def _get_apply_update(self) -> Callable:
        if self._apply_update_fn is not None:
            return self._apply_update_fn
        clip = (
            self.config.optimizer.gradient_clipping
            if self.config.optimizer
            else 0.0
        )
        optimizer = self.optimizer

        def apply_update(params, opt_state, grads, total_weight):
            with jax.named_scope("grad_norm"):
                grads = jax.tree.map(lambda g: g / total_weight, grads)
                gnorm = optax.global_norm(grads)
                if clip and clip > 0:
                    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * scale, grads)
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, gnorm

        # NOTE: grads (arg 2) are NOT donated — they have no same-shaped
        # output to alias (params/opt_state inputs already cover those), so
        # donating them only produces "donated buffers were not usable" noise.
        self._apply_update_fn = jax.jit(
            apply_update,
            donate_argnums=(0, 1),
            out_shardings=(
                self._trainable_sub(self._param_shardings),
                self._opt_state_shardings(),
                mesh_lib.replicated(self.mesh),
            ),
        )
        return self._apply_update_fn

    def _zero_grads(self):
        if not hasattr(self, "_zero_grads_fn") or self._zero_grads_fn is None:
            grad_dtype = jnp.dtype(self.config.grad_reduce_dtype)

            def zero_grads(p):
                return jax.tree.map(
                    lambda x: jnp.zeros(x.shape, grad_dtype), p
                )

            self._zero_grads_fn = jax.jit(
                zero_grads, out_shardings=self._grad_shardings()
            )
        return self._run("zero_grads", self._zero_grads_fn, None,
                         self._trainable_sub(self.params))

    def _run(self, program: str, fn: Callable, shape_key, *args, **static):
        """Dispatch a jitted program. The first call of a new (program,
        shape) traces and compiles (or loads from the persistent cache)
        inside the call: it gets a `train/compile` span and counts in
        `train_batch`'s `compiles`. A first call that raises has run
        nothing: the next one is a first call again."""
        key = (program, id(fn), shape_key)
        if key in self._programs_seen:
            return fn(*args, **static)
        self._programs_seen.add(key)
        try:
            with perf_tracer.span("train/compile", program=program,
                                  shape=str(shape_key)):
                return fn(*args, **static)
        except BaseException:
            self._programs_seen.discard(key)
            raise

    def train_batch(
        self,
        input_: dict[str, Any],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> dict[str, float]:
        # Rebind the ambient mesh so ops that trace lazily (ring attention's
        # shard_map) capture THIS engine's mesh even when several engines
        # with different strategies coexist in one process (actor + critic).
        mesh_lib.set_current_mesh(self.mesh)
        assert self.optimizer is not None, "engine has no optimizer"
        from areal_tpu.core import fault_injection

        # chaos seam: a trainer dying inside an optimizer step (weights
        # half-applied in HBM, nothing durable)
        fault_injection.fire("train.step", step=self._step_count)

        t_start = time.perf_counter()
        # env-gated device-trace window (AREAL_TPU_XPROF_DIR [+ _STEPS])
        perf_tracer.maybe_xprof_step(self._step_count, owner=id(self))
        span = perf_tracer.span
        seen_before = len(self._programs_seen)
        fused_head = self._wants_hidden(loss_fn)
        aux_stats: dict[str, float] = {}
        with perf_tracer.step_span("train/train_batch", self._step_count):
            with span("train/split_mbs"):
                mb_list = split_padded_tensor_dict_into_mb_list(
                    input_, self.config.mb_spec
                )
                weights = [float(loss_weight_fn(mb)) for mb in mb_list.mbs]
                total_weight = float(sum(weights)) or 1.0
                # tokens the micro-batches hold, padding included
                mb_tokens = [int(mb["cu_seqlens"][-1]) for mb in mb_list.mbs]
            if self._pp_size > 1:
                # pipelined path: all micro-batches stream through the pp
                # stages inside ONE jitted step (fill/steady/drain), one backward
                with span("train/upload_mb", tokens=sum(mb_tokens)):
                    stacked = self._stack_mbs(mb_list.mbs)
                pip_step = self._get_pipelined_grad_step(loss_fn)
                with span("train/grad_step", tokens=sum(mb_tokens)):
                    losses, mb_stats, acc = self._run(
                        "pip_grad_step", pip_step,
                        stacked["input_ids"].shape, self.params, stacked,
                        jnp.asarray(weights, jnp.float32),
                    )
                with span("train/read_stats"):
                    losses = list(np.asarray(losses))
                    w_arr = np.asarray(weights, np.float64)
                    for k, v in mb_stats.items():
                        aux_stats[k] = float(
                            (np.asarray(v, np.float64) * w_arr).sum()
                            / total_weight
                        )
            else:
                grad_step = self._get_grad_step(loss_fn)
                acc = self._zero_grads()
                losses = []
                mb_stat_list: list[dict] = []
                for mb, w, n_tok in zip(mb_list.mbs, weights, mb_tokens):
                    with span("train/upload_mb", tokens=n_tok):
                        dev_mb = self._device_mb(mb)
                    with span("train/grad_step", tokens=n_tok):
                        loss, mb_stats, acc = self._run_grad_step(
                            grad_step, fused_head, n_tok, acc, w, dev_mb
                        )
                    losses.append(loss)
                    # keep device arrays — float() here would sync per
                    # micro-batch and serialize the accumulation pipeline
                    mb_stat_list.append(mb_stats)
                with span("train/read_stats"):
                    # the first float() waits for every grad_step above
                    for mb_stats, w in zip(mb_stat_list, weights):
                        for k, v in mb_stats.items():
                            aux_stats[k] = aux_stats.get(k, 0.0) + float(v) * w
                aux_stats = {k: v / total_weight for k, v in aux_stats.items()}
            apply_update = self._get_apply_update()
            with span("train/apply_update"):
                new_trainable, self.opt_state, gnorm = self._run(
                    "apply_update", apply_update, None,
                    self._trainable_sub(self.params), self.opt_state, acc,
                    total_weight,
                )
                self.params = self._merge_trainable(self.params, new_trainable)
            # host work while the device runs the update: the share of the
            # flash kernels' block pairs that can hold a valid (query, key)
            # pair, and the share their walks take (the rest cost no step)
            live_pct, walked_pct = self._attn_block_pcts(mb_list.mbs)
            kept_sets, kept_bytes = self._remat_choice.get(
                (max(mb_tokens), fused_head), (0, 0)
            )
            with span("train/wait_device"):
                gnorm_f = float(gnorm)  # blocks until the step is done on device
            step_time = time.perf_counter() - t_start
            self._step_count += 1
            with span("train/step_stats"):
                # (the learning rate and the losses are small device reads)
                lr = float(self.lr_schedule(self._step_count))
                loss_avg = float(
                    sum(float(l) * w for l, w in zip(losses, weights))
                    / total_weight
                )
                stats = dict(
                    loss=loss_avg,
                    grad_norm=gnorm_f,
                    lr=lr,
                    n_mbs=len(mb_list.mbs),
                    update_steps=self._step_count,
                    # with n_tokens, the useful share of what the device was given
                    padded_tokens=float(sum(mb_tokens)),
                    attn_live_block_pct=live_pct,
                    attn_walked_block_pct=walked_pct,
                    # what the largest micro-batch's grad step keeps of its
                    # layers in place of recomputing them (0: full recompute)
                    remat_kept_sets=kept_sets,
                    remat_kept_bytes=float(kept_bytes),
                    # programs this step ran for the first time (a steady-state
                    # step that compiles names itself here)
                    compiles=len(self._programs_seen) - seen_before,
                    **aux_stats,
                )
                stats.update(self._throughput_stats(input_, step_time))
        return stats

    def _throughput_stats(
        self, input_: dict[str, Any], step_time: float
    ) -> dict[str, float]:
        """Emit the log-parseable throughput series the reference benchmark
        harness consumes (`time_perf/*` + `n_tokens`, BASELINE.md notes;
        realhf/system/master_worker.py:497-533) plus live TFLOP/s / MFU."""
        from areal_tpu.utils import stats_tracker
        from areal_tpu.utils.flops import peak_flops, train_flops_per_token

        mask = input_.get("attention_mask")
        if mask is not None:
            lens = np.asarray(mask).sum(axis=-1).astype(np.int64)
        else:
            lens = np.asarray([input_["input_ids"].shape[-1]])
        n_tokens = int(lens.sum())
        # mean causal context per token: sum L(L+1)/2 over seqs / total
        avg_ctx = float((lens * (lens + 1) / 2).sum() / max(n_tokens, 1))
        n_chips = self.mesh.devices.size if self.mesh is not None else 1
        tflops = (
            train_flops_per_token(self.model_config, avg_ctx) * n_tokens
        ) / step_time / 1e12
        tokens_per_sec_per_chip = n_tokens / step_time / n_chips
        out = dict(
            n_tokens=float(n_tokens),
            train_batch_time=step_time,
            tokens_per_sec_per_chip=tokens_per_sec_per_chip,
            tflops_per_chip=tflops / n_chips,
        )
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            # utilization only against a known chip's peak: a CPU run
            # reports none, and an unknown TPU is an error in peak_flops
            out["mfu"] = (
                tflops * 1e12 / n_chips / peak_flops(dev.device_kind)
            )
        # "throughput/n_tokens" (not bare "n_tokens"): algorithm engines
        # register n_tokens as a bool-mask *denominator* in the same scope.
        # A colocated critic engine prefixes its series so actor and critic
        # don't average into one stream on the shared default tracker.
        p = "critic/" if self.config.is_critic else ""
        scalars = {
            f"{p}time_perf/train_batch": step_time,
            f"{p}throughput/n_tokens": float(n_tokens),
            f"{p}throughput/tokens_per_sec_per_chip": tokens_per_sec_per_chip,
            f"{p}throughput/tflops_per_chip": tflops / n_chips,
        }
        if "mfu" in out:
            scalars[f"{p}throughput/mfu"] = out["mfu"]
        stats_tracker.scalar(**scalars)
        return out

    def eval_batch(
        self,
        input_: dict[str, Any],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ):
        mesh_lib.set_current_mesh(self.mesh)
        mb_list = split_padded_tensor_dict_into_mb_list(
            input_, self.config.mb_spec
        )
        key = ("eval", id(loss_fn))
        if key not in self._fwd_cache:
            model_cfg = self.model_config

            hidden_mode = self._wants_hidden(loss_fn)
            aux_mode = self._returns_aux(loss_fn)

            def eval_step(params, mb):
                params = self._to_model_layout(params)
                x = model_forward(
                    params,
                    mb["input_ids"],
                    mb["position_ids"],
                    mb["segment_ids"],
                    model_cfg,
                    return_hidden=hidden_mode,
                )
                if hidden_mode:
                    x = LMHead(x, params, model_cfg)
                res = loss_fn(x, mb)
                return res[0] if aux_mode else res

            self._fwd_cache[key] = jax.jit(eval_step)
        eval_step = self._fwd_cache[key]
        total_loss, total_w = 0.0, 0.0
        for mb in mb_list.mbs:
            w = float(loss_weight_fn(mb))
            loss = eval_step(self.params, self._device_mb(mb))
            total_loss += float(loss) * w
            total_w += w
        return total_loss / (total_w or 1.0)

    def forward(
        self,
        input_: dict[str, Any],
        output_seqlens: list[int] | None = None,
        post_hook: Callable | None = None,
        aggregate_fn: Callable | None = None,
    ):
        """No-grad forward with unpack → reorder → aggregate
        (parity: fsdp_engine.py:695-794)."""
        mesh_lib.set_current_mesh(self.mesh)
        mb_list = split_padded_tensor_dict_into_mb_list(
            input_, self.config.mb_spec
        )
        n_samples = input_["attention_mask"].shape[0]
        per_seq: list[np.ndarray | None] = [None] * n_samples
        if aggregate_fn is None:
            aggregate_fn = lambda xs: np.concatenate(xs, axis=0)  # noqa: E731

        if self._pp_size > 1:
            # pipelined no-grad forward: all mbs through the pp trunk at once
            key = ("fwd_pp", id(post_hook))
            if key not in self._fwd_cache:
                from areal_tpu.models.qwen2 import forward_pipelined

                model_cfg = self.model_config
                mesh = self.mesh

                hidden_mode = self._wants_hidden(post_hook)

                def fwd_pp(params, stacked):
                    if hidden_mode:
                        per_mb_fn = lambda h, mb: post_hook(  # noqa: E731
                            LMHead(h, params, model_cfg), mb
                        )
                    elif post_hook is not None:
                        per_mb_fn = post_hook
                    else:
                        per_mb_fn = lambda logits, mb: logits  # noqa: E731
                    return forward_pipelined(
                        params,
                        stacked["input_ids"],
                        stacked["position_ids"],
                        stacked["segment_ids"],
                        model_cfg,
                        mesh,
                        per_mb_fn=per_mb_fn,
                        mb_data=stacked,
                        head_mode="hidden" if hidden_mode else "logits",
                        virtual_pp=self._virtual_pp,
                    )

                self._fwd_cache[key] = jax.jit(fwd_pp)
            # All mbs were padded to a common bucket by _stack_mbs; their
            # cu_seqlens (for unpacking) reflect the ORIGINAL packing, and
            # rows past each mb's own tokens are pad output to discard.
            outs = np.asarray(
                self._fwd_cache[key](self.params, self._stack_mbs(mb_list.mbs))
            )
            for out, mb, sample_idx in zip(
                outs, mb_list.mbs, mb_list.forward_indices
            ):
                cu = np.asarray(mb["cu_seqlens"])
                seqs = unpack_sequence(out, cu)[: len(sample_idx)]
                for i, s in zip(sample_idx, seqs):
                    per_seq[i] = s
            return aggregate_fn(per_seq)

        key = ("fwd", id(post_hook))
        if key not in self._fwd_cache:
            model_cfg = self.model_config

            hidden_mode = self._wants_hidden(post_hook)

            def fwd_step(params, mb):
                params = self._to_model_layout(params)
                x = model_forward(
                    params,
                    mb["input_ids"],
                    mb["position_ids"],
                    mb["segment_ids"],
                    model_cfg,
                    return_hidden=hidden_mode,
                )
                if hidden_mode:
                    return post_hook(LMHead(x, params, model_cfg), mb)
                if post_hook is not None:
                    return post_hook(x, mb)
                return x

            self._fwd_cache[key] = jax.jit(fwd_step)
        fwd_step = self._fwd_cache[key]

        for mb, sample_idx in zip(mb_list.mbs, mb_list.forward_indices):
            n_tok = int(mb["cu_seqlens"][-1])
            with perf_tracer.span("train/upload_mb", tokens=n_tok):
                dev_mb = self._device_mb(mb)
            with perf_tracer.span("train/fwd_step", tokens=n_tok):
                out = self._run("fwd_step", fwd_step, n_tok, self.params, dev_mb)
            with perf_tracer.span("train/wait_device"):
                out = np.asarray(out)
            # Split mb output back into sequences; drop the pad tail (the
            # appended fake sequence is the last cu_seqlens entry if padded).
            cu = np.asarray(mb["cu_seqlens"])
            seqs = unpack_sequence(out, cu)[: len(sample_idx)]
            for i, s in zip(sample_idx, seqs):
                per_seq[i] = s
        return aggregate_fn(per_seq)
