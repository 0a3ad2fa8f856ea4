"""Qwen2-VL / Qwen2.5-VL vision tower + multimodal helpers (pure JAX).

Parity surface: the reference serves VLM rollouts through SGLang's Qwen2-VL
support (areal/workflow/vision_rlvr.py carries `image_data` to the server).
The TPU build's decode engine runs this tower at admission
(`JaxDecodeEngine._encode_images`), splices the outputs over the
`<|image_pad|>` positions (`splice_image_embeds`), prefills from embeddings
with m-rope tables (`mrope_positions`/`mrope_table`), and continues text
decode with a per-slot rotary offset.

Both HF families are supported, selected from the checkpoint's
vision_config (`VisionConfig.from_hf_dict`):
- **Qwen2-VL**: LayerNorm (with bias) norms, fc1/act/fc2 MLP (quick_gelu);
- **Qwen2.5-VL**: RMSNorm, SwiGLU (gate/up/down) MLP.

Data contract (matches the HF AutoProcessor exactly — verified against
Qwen2VLImageProcessor._preprocess): `pixel_values` rows arrive
WINDOW-MAJOR (each consecutive spatial_merge_size^2 rows are one merge
window) with voxels flattened (C, temporal_patch, patch, patch);
`patch_grid_coords` emits (h, w) per row in the same window-major order
(the permutation HF's rot_pos_emb applies). Producers holding row-major
patches can reorder with `window_major_order`.

TPU-first notes: the conv patch embed is a reshape+matmul (stride ==
kernel), everything else is dense einsum under jit with no
image-size-dependent Python control flow; the engine buckets patch-row
counts so XLA compiles once per bucket. Not yet implemented: Qwen2.5-VL's
windowed attention (full attention is used in every block — numerically
different for that family) — load_hf_vision_params refuses checkpoints
whose tensors it cannot map, so unsupported layouts fail loudly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "VisionConfig",
    "init_vision_params",
    "vision_param_logical_axes",
    "forward_vision",
    "splice_image_embeds",
    "window_major_order",
    "patch_grid_coords",
    "mrope_positions",
    "mrope_table",
]


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """Vision-tower geometry covering Qwen2-VL and Qwen2.5-VL."""

    embed_dim: int = 1280
    depth: int = 32
    num_heads: int = 16
    mlp_dim: int = 5120
    in_channels: int = 3
    patch_size: int = 14
    temporal_patch_size: int = 2
    spatial_merge_size: int = 2
    hidden_size: int = 3584  # language model hidden (merger output)
    norm_type: str = "layer"  # "layer" (2-VL) | "rms" (2.5-VL)
    mlp_type: str = "gelu"  # "gelu" (fc1/fc2) | "silu_glu" (gate/up/down)
    hidden_act: str = "quick_gelu"
    norm_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.temporal_patch_size * self.patch_size**2

    @property
    def merge_dim(self) -> int:
        return self.embed_dim * self.spatial_merge_size**2

    @classmethod
    def from_hf_dict(cls, d: dict) -> "VisionConfig":
        if "out_hidden_size" in d or "intermediate_size" in d:
            # Qwen2.5-VL layout: hidden_size is the EMBED dim, out_hidden_size
            # the language dim; RMSNorm + SwiGLU.
            embed = d.get("hidden_size", 1280)
            return cls(
                embed_dim=embed,
                depth=d.get("depth", 32),
                num_heads=d.get("num_heads", 16),
                mlp_dim=d.get("intermediate_size", int(embed * 4)),
                in_channels=d.get("in_channels", 3),
                patch_size=d.get("patch_size", 14),
                temporal_patch_size=d.get("temporal_patch_size", 2),
                spatial_merge_size=d.get("spatial_merge_size", 2),
                hidden_size=d.get("out_hidden_size", 3584),
                norm_type="rms",
                mlp_type="silu_glu",
                hidden_act="silu",
            )
        embed = d.get("embed_dim", 1280)
        return cls(
            embed_dim=embed,
            depth=d.get("depth", 32),
            num_heads=d.get("num_heads", 16),
            mlp_dim=int(embed * d.get("mlp_ratio", 4)),
            in_channels=d.get("in_channels", 3),
            patch_size=d.get("patch_size", 14),
            temporal_patch_size=d.get("temporal_patch_size", 2),
            spatial_merge_size=d.get("spatial_merge_size", 2),
            hidden_size=d.get("hidden_size", 3584),
            norm_type="layer",
            mlp_type="gelu",
            hidden_act=d.get("hidden_act", "quick_gelu"),
        )


def _norm_shapes(cfg: VisionConfig, dim: int) -> dict:
    s = {"scale": (dim,)}
    if cfg.norm_type == "layer":
        s["bias"] = (dim,)
    return s


def _block_shapes(cfg: VisionConfig) -> dict:
    D, M = cfg.embed_dim, cfg.mlp_dim
    mlp = (
        {
            "fc1_kernel": (D, M),
            "fc1_bias": (M,),
            "fc2_kernel": (M, D),
            "fc2_bias": (D,),
        }
        if cfg.mlp_type == "gelu"
        else {
            "gate_kernel": (D, M),
            "gate_bias": (M,),
            "up_kernel": (D, M),
            "up_bias": (M,),
            "down_kernel": (M, D),
            "down_bias": (D,),
        }
    )
    return {
        "norm1": _norm_shapes(cfg, D),
        "norm2": _norm_shapes(cfg, D),
        "attn": {
            "qkv_kernel": (D, 3, cfg.num_heads, cfg.head_dim),
            "qkv_bias": (3, cfg.num_heads, cfg.head_dim),
            "proj_kernel": (cfg.num_heads, cfg.head_dim, D),
            "proj_bias": (D,),
        },
        "mlp": mlp,
    }


def vision_param_shapes(cfg: VisionConfig) -> dict:
    block = _block_shapes(cfg)
    L = cfg.depth
    blocks = jax.tree.map(
        lambda s: (L, *s), block, is_leaf=lambda x: isinstance(x, tuple)
    )
    return {
        "patch_embed": {"kernel": (cfg.patch_dim, cfg.embed_dim)},
        "blocks": blocks,
        "merger": {
            "ln_q": _norm_shapes(cfg, cfg.embed_dim),
            "fc1_kernel": (cfg.merge_dim, cfg.merge_dim),
            "fc1_bias": (cfg.merge_dim,),
            "fc2_kernel": (cfg.merge_dim, cfg.hidden_size),
            "fc2_bias": (cfg.hidden_size,),
        },
    }


def vision_param_logical_axes(cfg: VisionConfig) -> dict:
    """Logical axes for the tower (same table as the decoder: heads/mlp
    shard over tp). Applied by JaxDecodeEngine.initialize."""

    def walk(tree, path=()):
        if isinstance(tree, tuple):
            name = path[-1]
            prefix = ("layers",) if path[0] == "blocks" else ()
            if name == "qkv_kernel":
                return (*prefix, "embed", None, "heads", "head_dim")
            if name == "qkv_bias":
                return (*prefix, None, "heads", "head_dim")
            if name == "proj_kernel":
                return (*prefix, "heads", "head_dim", "embed")
            if name in ("fc1_kernel", "gate_kernel", "up_kernel"):
                return (*prefix, "embed", "mlp")
            if name in ("fc2_kernel", "down_kernel"):
                return (*prefix, "mlp", "embed")
            if name in ("fc1_bias", "gate_bias", "up_bias"):
                return (*prefix, "mlp")
            return (*prefix,) + (None,) * (len(tree) - len(prefix))
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(vision_param_shapes(cfg))


def init_vision_params(cfg: VisionConfig, key, dtype=jnp.float32) -> dict:
    shapes = vision_param_shapes(cfg)
    n_leaves = len(
        jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    )
    keys = list(jax.random.split(key, n_leaves))

    def path_init(path, shape):
        name = path[-1]
        if name == "scale":
            return jnp.ones(shape, dtype)
        if name == "bias" or name.endswith("_bias"):
            return jnp.zeros(shape, dtype)
        k = keys.pop()
        return (jax.random.normal(k, shape) * 0.02).astype(dtype)

    def walk(tree, path=()):
        if isinstance(tree, tuple):
            return path_init(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(shapes)


# ---------------------------------------------------------------------------
# Host helpers: patch ordering, grid coords, m-rope positions
# ---------------------------------------------------------------------------


def window_major_order(grid_thw: np.ndarray, merge: int) -> np.ndarray:
    """Row-major -> window-major patch permutation (for producers that did
    NOT use the HF processor; HF pixel_values are already window-major)."""
    order = []
    base = 0
    for t, h, w in np.asarray(grid_thw).reshape(-1, 3):
        idx = np.arange(t * h * w).reshape(t, h, w)
        idx = (
            idx.reshape(t, h // merge, merge, w // merge, merge)
            .transpose(0, 1, 3, 2, 4)
            .reshape(-1)
        )
        order.append(base + idx)
        base += t * h * w
    return np.concatenate(order)


def patch_grid_coords(grid_thw: np.ndarray, merge: int) -> np.ndarray:
    """Per-patch (h, w) coordinates in WINDOW-MAJOR row order — the exact
    permutation HF's rot_pos_emb applies (verified against
    Qwen2VisionTransformerPretrainedModel.rot_pos_emb)."""
    coords = []
    for t, h, w in np.asarray(grid_thw).reshape(-1, 3):
        hh = np.broadcast_to(np.arange(h)[:, None], (h, w))
        ww = np.broadcast_to(np.arange(w)[None, :], (h, w))

        def wm(a):
            return (
                a.reshape(h // merge, merge, w // merge, merge)
                .transpose(0, 2, 1, 3)
                .reshape(-1)
            )

        c = np.stack([wm(hh), wm(ww)], axis=-1)  # [h*w, 2] window-major
        coords.append(np.tile(c, (t, 1)))
    return np.concatenate(coords)


def mrope_positions(
    input_ids: np.ndarray,
    image_grid_thw: np.ndarray,
    image_token_id: int,
    merge: int,
) -> tuple[np.ndarray, int]:
    """3-D (temporal, height, width) rope positions for one sequence plus
    the mrope position delta (parity: HF Qwen2VLModel.get_rope_index —
    image spans get grid coordinates offset by the running position; text
    resumes at span max + 1, so positions compress vs sequence length)."""
    ids = np.asarray(input_ids).reshape(-1)
    T = len(ids)
    pos = np.zeros((3, T), dtype=np.int32)
    grids = np.asarray(image_grid_thw).reshape(-1, 3)
    img_idx = 0
    cur = 0
    i = 0
    while i < T:
        if ids[i] == image_token_id and img_idx < len(grids):
            t, h, w = (int(x) for x in grids[img_idx])
            img_idx += 1
            lh, lw = h // merge, w // merge
            n = t * lh * lw
            n = min(n, T - i)  # truncated prompts keep a valid table
            tt = np.repeat(np.arange(t), lh * lw)[:n]
            hh = np.tile(np.repeat(np.arange(lh), lw), t)[:n]
            ww = np.tile(np.arange(lw), t * lh)[:n]
            pos[0, i : i + n] = cur + tt
            pos[1, i : i + n] = cur + hh
            pos[2, i : i + n] = cur + ww
            cur += max(t, lh, lw)
            i += n
        else:
            pos[:, i] = cur
            cur += 1
            i += 1
    return pos, cur - T


def mrope_table(
    positions3: np.ndarray,  # [3, T]
    head_dim: int,
    theta: float,
    sections: tuple[int, ...],  # mrope_section; sums to head_dim // 2
):
    """(cos, sin) [T, head_dim/2] with frequency j driven by the position
    dimension its m-rope section assigns (HF rope_scaling.mrope_section)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    sec_id = np.repeat(np.arange(len(sections)), np.asarray(sections))
    assert sec_id.shape[0] == half, (sections, half)
    p = np.asarray(positions3, dtype=np.float64)[sec_id, :].T  # [T, half]
    angles = p * inv[None, :]
    return (
        jnp.asarray(np.cos(angles), dtype=jnp.float32),
        jnp.asarray(np.sin(angles), dtype=jnp.float32),
    )


# ---------------------------------------------------------------------------
# Tower forward
# ---------------------------------------------------------------------------


def _rot_half(x):
    d2 = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d2:], x[..., :d2]], axis=-1)


def _vision_rope(grid_hw: jax.Array, head_dim: int, theta: float = 10000.0):
    """2-D rotary tables [N, head_dim]: first half of the frequency pairs
    rotated by the row coordinate, second half by the column."""
    d4 = head_dim // 4
    inv = 1.0 / (theta ** (jnp.arange(0, d4, dtype=jnp.float32) / d4))
    h = grid_hw[:, 0].astype(jnp.float32)[:, None] * inv[None, :]  # [N, d4]
    w = grid_hw[:, 1].astype(jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([h, w], axis=-1)  # [N, head_dim/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [N, head_dim]
    return jnp.cos(angles), jnp.sin(angles)


def _act(name: str):
    if name == "quick_gelu":
        return lambda x: x * jax.nn.sigmoid(1.702 * x)
    if name == "silu":
        return jax.nn.silu
    return lambda x: jax.nn.gelu(x, approximate=True)


def forward_vision(
    params: dict,
    pixel_values: jax.Array,  # [N, patch_dim] WINDOW-MAJOR rows (HF format)
    grid_coords: jax.Array,  # [N, 2] (h, w) per patch, window-major
    cfg: VisionConfig,
    valid: jax.Array | None = None,  # [N] bool for bucket padding
) -> jax.Array:
    """[N, patch_dim] patches -> [N / merge^2, hidden_size] embeddings."""
    compute = pixel_values.dtype
    x = pixel_values @ params["patch_embed"]["kernel"].astype(compute)
    cos, sin = _vision_rope(grid_coords, cfg.head_dim)
    N = x.shape[0]
    nH, hd = cfg.num_heads, cfg.head_dim
    mask = None if valid is None else (valid[None, :] & valid[:, None])
    act = _act(cfg.hidden_act)

    def norm(v, p):
        v32 = v.astype(jnp.float32)
        if cfg.norm_type == "layer":
            mu = jnp.mean(v32, axis=-1, keepdims=True)
            var = jnp.var(v32, axis=-1, keepdims=True)
            out = (v32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
            out = out * p["scale"] + p["bias"]
        else:
            var = jnp.mean(jnp.square(v32), axis=-1, keepdims=True)
            out = v32 * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
        return out.astype(v.dtype)

    def mlp(h, p):
        if cfg.mlp_type == "gelu":
            h = act(h @ p["fc1_kernel"].astype(compute) + p["fc1_bias"].astype(compute))
            return h @ p["fc2_kernel"].astype(compute) + p["fc2_bias"].astype(compute)
        gate = h @ p["gate_kernel"].astype(compute) + p["gate_bias"].astype(compute)
        up = h @ p["up_kernel"].astype(compute) + p["up_bias"].astype(compute)
        return (jax.nn.silu(gate) * up) @ p["down_kernel"].astype(
            compute
        ) + p["down_bias"].astype(compute)

    def block(x, p):
        h = norm(x, p["norm1"])
        qkv = jnp.einsum("nd,dshe->nshe", h, p["attn"]["qkv_kernel"].astype(compute))
        qkv = qkv + p["attn"]["qkv_bias"].astype(compute)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [N, nH, hd]
        c = cos[:, None, :].astype(compute)
        s = sin[:, None, :].astype(compute)
        q = q * c + _rot_half(q) * s
        k = k * c + _rot_half(k) * s
        scores = jnp.einsum("nhd,mhd->hnm", q, k).astype(jnp.float32) / np.sqrt(hd)
        if mask is not None:
            scores = jnp.where(mask[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(compute)
        att = jnp.einsum("hnm,mhd->nhd", probs, v)
        x = x + jnp.einsum(
            "nhd,hde->ne", att, p["attn"]["proj_kernel"].astype(compute)
        ) + p["attn"]["proj_bias"].astype(compute)
        x = x + mlp(norm(x, p["norm2"]), p["mlp"])
        return x, None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = norm(x, params["merger"]["ln_q"])
    m2 = cfg.spatial_merge_size**2
    x = x.reshape(N // m2, m2 * cfg.embed_dim)
    h = jax.nn.gelu(
        x @ params["merger"]["fc1_kernel"].astype(compute)
        + params["merger"]["fc1_bias"].astype(compute),
        approximate=True,
    )
    return (
        h @ params["merger"]["fc2_kernel"].astype(compute)
        + params["merger"]["fc2_bias"].astype(compute)
    )


def splice_image_embeds(
    token_embeds: jax.Array,  # [T, H]
    input_ids: jax.Array,  # [T]
    image_embeds: jax.Array,  # [K, H] (>= #image-pad tokens; extra ignored)
    image_token_id: int,
) -> jax.Array:
    """Replace embeddings at `<|image_pad|>` positions with vision vectors,
    in order. Pure gather/where — jit-safe for any pad-count <= K."""
    is_img = input_ids == image_token_id  # [T]
    # k-th image position gets image_embeds[k]
    order = jnp.cumsum(is_img.astype(jnp.int32)) - 1  # [T], -1 before first
    order = jnp.clip(order, 0, image_embeds.shape[0] - 1)
    gathered = image_embeds[order].astype(token_embeds.dtype)  # [T, H]
    return jnp.where(is_img[:, None], gathered, token_embeds)
