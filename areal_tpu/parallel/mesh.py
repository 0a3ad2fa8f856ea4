"""Device mesh construction: ParallelStrategy → jax.sharding.Mesh.

This is the TPU replacement for the reference's process-group plumbing
(realhf/base/topology.py ProcessTopology/ParallelGrid, areal/utils/fsdp/
parallel.py ParallelHelper.world_mesh): one named mesh, and every
parallelism dimension becomes sharding annotations over its axes. XLA then
inserts the collectives (psum/all-gather/reduce-scatter/all-to-all) that the
reference issues by hand through NCCL.

Axis layout (order matters — later axes vary fastest, i.e. are nearest
neighbours on the ICI torus):

    ("pp", "dp", "sp", "tp")

- "tp"  innermost: tensor-parallel collectives (per-layer all-reduce /
  reduce-scatter) are the most latency-sensitive → adjacent chips.
- "sp"  context/sequence parallelism (ring attention all-to-alls).
- "dp"  data parallel; parameters are additionally sharded over this axis
  ZeRO-3-style when fsdp is enabled (the reference's FSDP2 dim).
- "pp"  outermost: pipeline stages communicate least often.

Expert parallelism folds over ("dp", "sp") — the reference likewise carves
EP out of the dp×cp ranks (Megatron MoE parallel folding,
areal/api/alloc_mode.py expert_data_parallel_size).
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from areal_tpu.api.alloc_mode import ParallelStrategy

AXIS_PP = "pp"
AXIS_DP = "dp"
AXIS_SP = "sp"
AXIS_TP = "tp"
MESH_AXES = (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_TP)

# Ambient mesh: engines register their mesh here so ops deep inside the
# jitted model (ring attention's shard_map) can reach it without threading a
# Mesh through every pure function signature.
_CURRENT_MESH: Mesh | None = None

# Per-thread override. Two engines with DIFFERENT topologies can share a
# process (COLOCATE: the train engine's 8-chip mesh + a tp-sharded decode
# engine over a subset), each running compute on its own thread. A traced
# `constrain` must resolve the mesh of the engine whose thread is tracing,
# never the other engine's — a constraint naming devices the operand doesn't
# live on is a compile error. An entry may be None: that is an explicit
# "trace with no ambient mesh" binding (unsharded decode engine), distinct
# from an empty stack (fall through to the process-global).
_TLS = threading.local()


def set_current_mesh(mesh: Mesh | None) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


@contextlib.contextmanager
def mesh_scope(mesh: Mesh | None):
    """Bind the ambient mesh for the current thread (None = no mesh)."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(mesh)
    try:
        yield
    finally:
        stack.pop()


def current_mesh() -> Mesh | None:
    stack = getattr(_TLS, "stack", None)
    if stack:
        return stack[-1]
    return _CURRENT_MESH


def clear_current_mesh_if(mesh: Mesh) -> None:
    """Unset the process-global ambient mesh iff it is `mesh` (engine
    teardown hygiene — never clobbers a mesh some other engine installed)."""
    global _CURRENT_MESH
    if _CURRENT_MESH is mesh:
        _CURRENT_MESH = None


def build_mesh(
    strategy: ParallelStrategy, devices: list | None = None
) -> Mesh:
    """Build the named device mesh for a parallel strategy.

    `devices` defaults to all global devices; their count must equal the
    strategy's world size.
    """
    if devices is None:
        devices = jax.devices()
    shape = (
        strategy.pp_size,
        strategy.dp_size,
        strategy.cp_size,
        strategy.tp_size,
    )
    world = int(np.prod(shape))
    if len(devices) != world:
        raise ValueError(
            f"strategy world size {world} ({strategy}) != device count "
            f"{len(devices)}"
        )
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def build_hybrid_mesh(
    strategy: ParallelStrategy,
    *,
    num_slices: int,
    dcn_axes: tuple[str, ...] = (AXIS_PP,),
    devices: list | None = None,
) -> Mesh:
    """Hybrid ICI/DCN mesh across `num_slices` accelerator slices.

    Multi-pod TPU topologies have two interconnects: the per-slice ICI
    torus and the much slower data-center network (DCN) between slices.
    A mesh axis placed across the slice boundary pays DCN latency for its
    collectives, so only the least-chatty axes belong there: "pp" (one
    stage-boundary activation hop per microbatch per round) and, for very
    large fleets, an outer "dp" split (one gradient reduce per step).
    Everything else keeps its ICI adjacency — the axis order *inside* a
    slice is unchanged from `build_mesh`.

    Each axis named in `dcn_axes` (in order) absorbs a factor of
    `num_slices`: its mesh dimension splits into (dcn_factor ×
    within-slice), with the slice coordinate varying slowest, exactly the
    convention of `jax.experimental.mesh_utils.create_hybrid_device_mesh`.
    That helper is used verbatim when the runtime exposes per-device
    `slice_index` (real multi-slice TPU); otherwise — CPU test fixtures,
    `--plan-check` on a dev box — the same device layout is emulated by
    treating consecutive device granules as slices, which produces an
    identically-shaped program for AOT compilation.
    """
    if devices is None:
        devices = jax.devices()
    shape = (
        strategy.pp_size,
        strategy.dp_size,
        strategy.cp_size,
        strategy.tp_size,
    )
    world = int(np.prod(shape))
    if len(devices) != world:
        raise ValueError(
            f"strategy world size {world} ({strategy}) != device count "
            f"{len(devices)}"
        )
    if num_slices <= 1:
        return build_mesh(strategy, devices)
    if world % num_slices:
        raise ValueError(
            f"world size {world} not divisible by num_slices={num_slices}"
        )
    import math

    dcn = [1] * len(MESH_AXES)
    remaining = num_slices
    for name in dcn_axes:
        if name not in MESH_AXES:
            raise ValueError(f"unknown dcn axis {name!r}; mesh axes are "
                             f"{MESH_AXES}")
        i = MESH_AXES.index(name)
        f = math.gcd(shape[i], remaining)
        dcn[i] = f
        remaining //= f
    if remaining != 1:
        raise ValueError(
            f"cannot factor num_slices={num_slices} over dcn_axes="
            f"{tuple(dcn_axes)} of mesh shape {shape}: {remaining} left over"
        )
    ici = tuple(n // d for n, d in zip(shape, dcn))
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) == num_slices:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici, tuple(dcn), devices=devices
        )
        return Mesh(dev_array, MESH_AXES)
    # Faked multi-slice topology: consecutive granules of world/num_slices
    # devices stand in for slices. Granules fill the DCN grid in C order,
    # devices inside a granule fill the ICI grid; interleaving the two
    # grids per axis (dcn coordinate slowest) reproduces the hybrid
    # layout create_hybrid_device_mesh would build.
    arr = np.asarray(devices).reshape(tuple(dcn) + ici)
    k = len(MESH_AXES)
    order = [x for i in range(k) for x in (i, k + i)]
    return Mesh(arr.transpose(order).reshape(shape), MESH_AXES)


def strategy_from_mesh(mesh: Mesh) -> ParallelStrategy:
    """Inverse of build_mesh (for logging / validation)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ParallelStrategy(
        pipeline_parallel_size=sizes.get(AXIS_PP, 1),
        data_parallel_size=sizes.get(AXIS_DP, 1),
        context_parallel_size=sizes.get(AXIS_SP, 1),
        tensor_parallel_size=sizes.get(AXIS_TP, 1),
    )


# ---------------------------------------------------------------------------
# Logical-axis sharding rules (t5x/maxtext convention): model code annotates
# parameters/activations with *logical* axis names; these rules map them to
# mesh axes. Changing the parallel layout = changing this table, not the
# model. This one table subsumes the reference's DTensor TP plan
# (areal/utils/fsdp/parallel.py:255-396), Megatron Column/RowParallelLinear
# (realhf/impl/model/parallelism/tensor_parallel/modules.py), and Ulysses
# sequence sharding (areal/utils/ulysses.py).
# ---------------------------------------------------------------------------

LogicalRules = tuple[tuple[str, str | tuple[str, ...] | None], ...]

# fsdp=True: shard params' largest logical dims over the dp axis (ZeRO-3).
# pp=True: shard the scanned layer stack over the "pp" axis — each pipeline
# stage holds L/pp layers; the engine routes compute through
# parallel/pipeline.py's GPipe shard_map (forward_pipelined) so stages
# execute their own layers instead of gathering the full stack.
def default_rules(fsdp: bool = True, pp: bool = False) -> LogicalRules:
    fsdp_axis = AXIS_DP if fsdp else None
    return (
        # activations
        ("batch", AXIS_DP),
        ("seq", AXIS_SP),
        ("tokens", (AXIS_DP, AXIS_SP)),  # packed 1-D token streams
        # pipeline: the leading stage dim of stage-stacked activations /
        # layer stacks ([pp, ...] arrays inside parallel/pipeline.py)
        ("stages", AXIS_PP),
        ("act_embed", None),
        ("act_heads", AXIS_TP),
        ("act_kv_heads", AXIS_TP),
        ("act_mlp", AXIS_TP),
        ("act_vocab", AXIS_TP),
        # parameters
        ("vocab", AXIS_TP),
        ("embed", fsdp_axis),
        ("heads", AXIS_TP),
        ("kv_heads", AXIS_TP),
        ("head_dim", None),
        ("mlp", AXIS_TP),
        ("experts", AXIS_DP),  # EP folds over dp ranks
        ("layers", AXIS_PP if pp else None),
        ("norm", None),
    )


def logical_to_mesh_axes(
    logical_axes: tuple[str | None, ...], rules: LogicalRules
) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec via `rules`."""
    table = dict(rules)
    out = []
    used: set[str] = set()
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        axis = table.get(name)
        # A mesh axis may shard at most one dim of a given array.
        if axis is not None and axis in used:
            axis = None
        if axis is not None:
            used.add(axis) if isinstance(axis, str) else used.update(axis)
        out.append(axis)
    return PartitionSpec(*out)


def named_sharding(
    mesh: Mesh, logical_axes: tuple[str | None, ...], rules: LogicalRules | None = None
) -> NamedSharding:
    rules = rules if rules is not None else default_rules()
    return NamedSharding(mesh, logical_to_mesh_axes(logical_axes, rules))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [B, T, ...] batches: batch over dp, sequence over sp."""
    return NamedSharding(mesh, PartitionSpec(AXIS_DP, AXIS_SP))


def packed_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for packed 1-D token streams: tokens over (dp, sp)."""
    return NamedSharding(mesh, PartitionSpec((AXIS_DP, AXIS_SP)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def constrain(
    x: jax.Array, *logical_axes: str | None, mesh: Mesh | None = None
) -> jax.Array:
    """Pin an activation's layout by logical axis names (no-op without an
    ambient mesh).

    Model code calls this at layer boundaries so GSPMD's propagation never
    has to *guess* activation layouts — an unconstrained backward pass is
    where "involuntary full rematerialization" reshards come from: XLA
    derives one layout for a scan residual from the forward and a different
    one from the gradient flow, then replicates to bridge them.

    `mesh` overrides the ambient mesh — parallel/pipeline.py pins its
    stage-stacked carries against the engine mesh while the stage bodies
    trace under mesh_scope(None).
    """
    if mesh is None:
        mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_mesh_axes(logical_axes, default_rules())
    # A logical axis mapping to no mesh axis is deliberately PINNED
    # replicated (None) — that is the layout statement. But a mesh axis that
    # doesn't divide the dim (tiny test shapes) becomes UNCONSTRAINED —
    # "let GSPMD choose" — because pinning replicated there would force an
    # all-gather the caller never asked for.
    fixed = []
    for dim, axes in zip(x.shape, spec):
        if axes is None:
            fixed.append(None)
            continue
        group = (axes,) if isinstance(axes, str) else tuple(axes)
        size = 1
        for a in group:
            size *= mesh.shape.get(a, 1)
        fixed.append(axes if dim % size == 0 else PartitionSpec.UNCONSTRAINED)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*fixed))
    )
