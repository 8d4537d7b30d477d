"""Building blocks shared by all architectures: sharding helper, norms,
embeddings, rotary embeddings, MLPs (dense + swiglu)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ModelConfig

# ---------------------------------------------------------------------------
# Logical sharding
# ---------------------------------------------------------------------------
# Logical axis names used throughout the models; the mesh mapping below is the
# single place where logical axes bind to physical mesh axes.  'batch' spreads
# over the pure-data axes ('pod','data' when the pod axis is used for DP,
# 'data' otherwise); 'model' carries tensor parallelism.

LOGICAL_RULES = {
    "batch": ("pod", "data"),
    "seq": None,                # sequences are replicated except for long-context decode
    "seq_sp": ("model",),       # megatron-style sequence parallelism at block edges
    "seq_kv": ("data",),        # KV-cache sequence dim for B=1 long-context decode
    "d_model": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "moe_cap": ("data",),       # MoE capacity dim: shard expert token-slots over data
    "stage": ("pod",),          # pipeline stage axis (paper technique)
}


def _resolve(axis, mesh_axes):
    if axis is None:
        return None
    rule = LOGICAL_RULES.get(axis, None)
    if rule is None:
        return None
    picked = tuple(a for a in rule if a in mesh_axes)
    if not picked:
        return None
    return picked if len(picked) > 1 else picked[0]


def shard(x: jax.Array, *logical_axes) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names; no-op without a mesh.
    Axes whose dimension is not divisible by the mesh-axis size are dropped
    (uneven constraints trigger GSPMD resharding storms)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    mesh_axes = set(am.axis_names) - set(getattr(am, "manual_axes", ()) or ())
    entries = []
    used: set = set()
    for dim, a in enumerate(logical_axes):
        r = _resolve(a, mesh_axes)
        if r is not None:
            axes = r if isinstance(r, tuple) else (r,)
            if used & set(axes):
                r = None  # a mesh axis can appear at most once per spec
            else:
                size = 1
                for ax in axes:
                    size *= am.shape[ax]
                if x.shape[dim] % size:
                    r = None
                else:
                    used |= set(axes)
        entries.append(r)
    spec = P(*entries)
    return jax.lax.with_sharding_constraint(x, spec)


def logical_sharding(logical_axes, mesh) -> jax.sharding.NamedSharding:
    """NamedSharding for parameter/batch placement from logical axis names."""
    mesh_axes = set(mesh.axis_names)
    spec = P(*(_resolve(a, mesh_axes) for a in logical_axes))
    return jax.sharding.NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(max(fan, 1))
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def embed_init(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(0.02, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float, use_pallas: bool = False) -> jax.Array:
    if use_pallas:
        from ..kernels import ops as kops

        return kops.rmsnorm(x, scale, eps=eps)
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * scale.astype(dt) + bias.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    ff = d_ff if d_ff is not None else cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    pdt = cfg.jparam_dtype
    if cfg.act == "swiglu":
        return {
            "wi": dense_init(ks[0], (d, ff), pdt),
            "wg": dense_init(ks[1], (d, ff), pdt),
            "wo": dense_init(ks[2], (ff, d), pdt),
        }
    return {
        "wi": dense_init(ks[0], (d, ff), pdt),
        "wo": dense_init(ks[2], (ff, d), pdt),
    }


def mlp(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if cfg.act == "swiglu":
        h = jnp.einsum("...d,df->...f", x, params["wi"].astype(dt))
        g = jnp.einsum("...d,df->...f", x, params["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    else:
        h = jnp.einsum("...d,df->...f", x, params["wi"].astype(dt))
        h = jax.nn.gelu(h)
    h = shard(h, "batch", "seq", "ff")
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    pdt = cfg.jparam_dtype
    out = {"tok": embed_init(k1, (cfg.vocab_size, cfg.d_model), pdt)}
    if not cfg.tie_embeddings:
        out["unembed"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), pdt)
    return out


def embed(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(params["tok"].astype(cfg.jdtype), tokens, axis=0)
    return shard(x, "batch", "seq", "d_model")


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["tok"].astype(x.dtype).T
    else:
        w = params["unembed"].astype(x.dtype)
    logits = jnp.einsum("...d,dv->...v", x, w)
    return shard(logits, "batch", "seq", "vocab")
