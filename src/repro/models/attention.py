"""Attention: GQA, qk-norm, biases, sliding windows, KV caches.

Two execution paths:

 - ``plain_attention``  : einsum softmax attention, used for short sequences
   (< ~2k) and cross-attention.
 - ``blocked_attention``: flash-style online-softmax over a *static schedule of
   (query-block, key-block) pairs*.  Only pairs that intersect the causal /
   sliding-window band are enumerated, so the compiled HLO performs S^2/2
   FLOPs for causal attention and S*W for SWA — the same work a Pallas/TPU
   flash kernel does, which keeps the dry-run roofline honest.  Memory stays
   bounded by one (Bq x Bk) score block per step.

Decode uses a separate single-token path over a (possibly ring-buffered) KV
cache (:func:`decode_attention`).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import apply_rope, dense_init, rms_norm, shard

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, n_heads: Optional[int] = None,
                   n_kv: Optional[int] = None, head_dim: Optional[int] = None) -> dict:
    H = n_heads or cfg.n_heads
    K = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    pdt = cfg.jparam_dtype
    p = {
        "wq": dense_init(ks[0], (d, H, hd), pdt, fan_in=d),
        "wk": dense_init(ks[1], (d, K, hd), pdt, fan_in=d),
        "wv": dense_init(ks[2], (d, K, hd), pdt, fan_in=d),
        "wo": dense_init(ks[3], (H, hd, d), pdt, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), pdt)
        p["bk"] = jnp.zeros((K, hd), pdt)
        p["bv"] = jnp.zeros((K, hd), pdt)
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((hd,), pdt)
        p["k_scale"] = jnp.ones((hd,), pdt)
    return p


def _project_qkv(params, x, kv_x, cfg: ModelConfig, positions, kv_positions,
                 rope: bool = True):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", kv_x, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", kv_x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Plain attention (short sequences / cross attention)
# ---------------------------------------------------------------------------

def plain_attention(q, k, v, *, causal: bool, window: Optional[int],
                    q_positions=None, k_positions=None) -> jax.Array:
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    q5 = q.reshape(B, S, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q5.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal or window is not None:
        pq = q_positions if q_positions is not None else jnp.arange(S)
        pk = k_positions if k_positions is not None else jnp.arange(T)
        mask = jnp.ones((S, T), bool)
        if causal:
            mask &= pq[:, None] >= pk[None, :]
        if window is not None:
            mask &= pq[:, None] - pk[None, :] < window
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# Blocked attention with a static block-pair schedule
# ---------------------------------------------------------------------------

def _block_pairs(nq: int, nk: int, bq: int, bk: int, causal: bool,
                 window: Optional[int]) -> list:
    """Static (qi, ki) schedule: only blocks intersecting the visibility band."""
    pairs = []
    for qi in range(nq):
        q_lo, q_hi = qi * bq, qi * bq + bq - 1
        for ki in range(nk):
            k_lo, k_hi = ki * bk, ki * bk + bk - 1
            if causal and k_lo > q_hi:
                continue  # entirely in the future
            if window is not None and k_hi < q_lo - window + 1:
                continue  # entirely outside the window
            pairs.append((qi, ki))
    return pairs


def _mesh_model_size() -> int:
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "model" not in am.axis_names:
        return 1
    return am.shape["model"]


def seq_parallel_attention(q, k, v, *, causal: bool, window: Optional[int],
                           block_q: int = 512, block_k: int = 512) -> jax.Array:
    """Sequence-parallel blocked attention (manual over 'model').

    For architectures whose head count does not divide the model axis (56, 40,
    20 heads on a 16-way axis), GSPMD falls back to head_dim sharding, which
    puts an all-reduce after EVERY score/PV block einsum of the pair scan.
    Here instead each model shard owns a contiguous q-sequence chunk, K/V are
    all-gathered once (tens of MB), and the pair scan runs entirely locally.
    Cost: the static pair schedule cannot be causally pruned per shard (the
    offset is traced), so attention does rectangle S_loc x T work — 2x the
    triangle — which is still far cheaper than per-pair collectives.
    K/V are staged through f32 around the gather: XLA:CPU crashes compiling
    bf16 collectives (AllReducePromotion pass bug)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    msize = _mesh_model_size()
    S_loc = S // msize
    nq, nk = S_loc // block_q, T // block_k
    from jax.sharding import PartitionSpec as P

    q5 = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,hd)
    q5 = jax.lax.with_sharding_constraint(q5, P(None, None, None, "model", None))
    k32 = jax.lax.with_sharding_constraint(
        k.astype(jnp.float32), P(None, "model", None, None))
    v32 = jax.lax.with_sharding_constraint(
        v.astype(jnp.float32), P(None, "model", None, None))

    def local(q_l, k_l, v_l):
        kf = jax.lax.all_gather(k_l, "model", axis=1, tiled=True)   # (B,T,K,hd)
        vf = jax.lax.all_gather(v_l, "model", axis=1, tiled=True)
        q_off = jax.lax.axis_index("model") * S_loc
        scale = 1.0 / math.sqrt(hd)

        m0 = jnp.full((B, K, G, S_loc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, S_loc), jnp.float32)
        a0 = jnp.zeros((B, K, G, S_loc, hd), jnp.float32)

        def step(carry, idx):
            m, l, acc = carry
            qi, ki = idx // nk, idx % nk
            qs = qi * block_q
            ks = ki * block_k
            qb = jax.lax.dynamic_slice_in_dim(q_l, qs, block_q, axis=3)
            kb = jax.lax.dynamic_slice_in_dim(kf, ks, block_k, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vf, ks, block_k, axis=1)
            s_blk = jnp.einsum("bkgqh,btkh->bkgqt", qb.astype(jnp.float32),
                               kb) * scale
            pq = q_off + qs + jnp.arange(block_q)
            pk = ks + jnp.arange(block_k)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= pq[:, None] >= pk[None, :]
            if window is not None:
                mask &= pq[:, None] - pk[None, :] < window
            s_blk = jnp.where(mask, s_blk, NEG_INF)
            m_blk = s_blk.max(axis=-1)
            p_blk = jnp.exp(s_blk - m_blk[..., None])
            l_blk = p_blk.sum(axis=-1)
            a_blk = jnp.einsum("bkgqt,btkh->bkgqh", p_blk, vb)
            m_old = jax.lax.dynamic_slice_in_dim(m, qs, block_q, axis=3)
            l_old = jax.lax.dynamic_slice_in_dim(l, qs, block_q, axis=3)
            a_old = jax.lax.dynamic_slice_in_dim(acc, qs, block_q, axis=3)
            m_new = jnp.maximum(m_old, m_blk)
            alpha = jnp.exp(m_old - m_new)
            beta = jnp.exp(m_blk - m_new)
            l_new = alpha * l_old + beta * l_blk
            a_new = alpha[..., None] * a_old + beta[..., None] * a_blk
            m = jax.lax.dynamic_update_slice_in_dim(m, m_new, qs, axis=3)
            l = jax.lax.dynamic_update_slice_in_dim(l, l_new, qs, axis=3)
            acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, qs, axis=3)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      jnp.arange(nq * nk))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q_l.dtype)

    mapped = jax.shard_map(
        local,
        in_specs=(P(None, None, None, "model", None),
                  P(None, "model", None, None), P(None, "model", None, None)),
        out_specs=P(None, None, None, "model", None),
        axis_names={"model"}, check_vma=False)
    out = mapped(q5, k32, v32)                                # (B,K,G,S,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def blocked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      block_q: int = 512, block_k: int = 512) -> jax.Array:
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if S % block_q or T % block_k:
        return plain_attention(q, k, v, causal=causal, window=window)
    msize = _mesh_model_size()
    if msize > 1 and H % msize != 0 and S == T and S % msize == 0 \
            and (S // msize) % 128 == 0:
        # head count does not divide the model axis: head/hd sharding would
        # put collectives inside the pair scan — go sequence-parallel instead
        bq = min(block_q, S // msize)
        return seq_parallel_attention(q, k, v, causal=causal, window=window,
                                      block_q=bq, block_k=block_k)
    nq, nk = S // block_q, T // block_k
    pairs = _block_pairs(nq, nk, block_q, block_k, causal, window)
    qi_arr = jnp.array([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.array([p[1] for p in pairs], jnp.int32)

    q5 = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,hd)
    q5 = shard(q5, "batch", None, None, None, None)
    k = shard(k, "batch", None, None, None)
    v = shard(v, "batch", None, None, None)
    scale = 1.0 / math.sqrt(hd)

    # Per-q-block segments (unrolled): each q block scans over its own static
    # in-band k-block list with a SMALL (bq-sized) online-softmax carry.
    # Versus one scan over all (qi, ki) pairs updating a full-S carry, this
    # removes the per-step dynamic-update-slice + carry copies of a (B,K,G,S,
    # hd) fp32 buffer — ~4 TB of HBM traffic on an 80-layer model — while
    # keeping exact causal/SWA flop pruning and static trip counts.
    pairs_by_q: dict = {}
    for qi, ki in pairs:
        pairs_by_q.setdefault(qi, []).append(ki)

    def run_qblock(qi: int, kis: list) -> jax.Array:
        qs = qi * block_q
        qb = jax.lax.slice_in_dim(q5, qs, qs + block_q, axis=3)      # (B,K,G,bq,hd)
        qb = qb.astype(jnp.float32)
        m0 = jnp.full((B, K, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, K, G, block_q, hd), jnp.float32)

        def step(carry, ki):
            m, l, acc = carry
            ks = ki * block_k
            kb = jax.lax.dynamic_slice_in_dim(k, ks, block_k, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ks, block_k, axis=1)
            s_blk = jnp.einsum("bkgqh,btkh->bkgqt", qb,
                               kb.astype(jnp.float32)) * scale       # (B,K,G,bq,bk)
            pq = qs + jnp.arange(block_q)
            pk = ks + jnp.arange(block_k)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= pq[:, None] >= pk[None, :]
            if window is not None:
                mask &= pq[:, None] - pk[None, :] < window
            s_blk = jnp.where(mask, s_blk, NEG_INF)
            m_blk = s_blk.max(axis=-1)
            p_blk = jnp.exp(s_blk - m_blk[..., None])
            m_new = jnp.maximum(m, m_blk)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_blk - m_new)
            l = alpha * l + beta * p_blk.sum(axis=-1)
            a_blk = jnp.einsum("bkgqt,btkh->bkgqh", p_blk, vb.astype(jnp.float32))
            acc = alpha[..., None] * acc + beta[..., None] * a_blk
            return (m_new, l, acc), None

        if len(kis) == 1:
            (m, l, acc), _ = step((m0, l0, a0), jnp.int32(kis[0]))
        else:
            (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                          jnp.asarray(kis, jnp.int32))
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]                                    # (B,K,G,bq,hd)

    outs = [run_qblock(qi, pairs_by_q[qi]) for qi in sorted(pairs_by_q)]
    out = jnp.concatenate(outs, axis=3)                              # (B,K,G,S,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Full-sequence attention entry point (train / prefill)
# ---------------------------------------------------------------------------

def attention(params, x, cfg: ModelConfig, *, positions=None, causal=True,
              window: Optional[int] = None, kv_x=None, rope=True) -> jax.Array:
    B, S, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    T = kv_x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    kv_positions = positions if kv_x is x else jnp.arange(T)[None, :]
    q, k, v = _project_qkv(params, x, kv_x, cfg, positions, kv_positions, rope=rope)
    if cfg.use_pallas and S > 1024 and S % 512 == 0 and T % 512 == 0:
        from ..kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    elif S <= 2048 or S % 512 or T % 512:
        out = plain_attention(q, k, v, causal=causal, window=window)
    else:
        out = blocked_attention(q, k, v, causal=causal, window=window,
                                block_q=min(cfg.attn_chunk, 512),
                                block_k=min(cfg.attn_chunk, 512))
    dt = x.dtype
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(y, "batch", "seq", "d_model")


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array           # (B, C, K, hd)  C = cache capacity (seq_len or window)
    v: jax.Array
    pos: jax.Array         # (B,) next absolute position to write
    positions: jax.Array   # (B, C) absolute position stored in each slot (-1 empty)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               n_kv: Optional[int] = None, head_dim: Optional[int] = None,
               dtype=None) -> KVCache:
    K = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.head_dim
    dt = dtype or cfg.jdtype
    return KVCache(
        k=jnp.zeros((batch, capacity, K, hd), dt),
        v=jnp.zeros((batch, capacity, K, hd), dt),
        pos=jnp.zeros((batch,), jnp.int32),
        positions=jnp.full((batch, capacity), -1, jnp.int32),
    )


def cache_from_prefill(cfg: ModelConfig, k, v, window: Optional[int] = None) -> KVCache:
    """Build a cache holding full-prefill K/V (optionally only the last window)."""
    B, S = k.shape[0], k.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    if window is not None and S > window:
        k, v = k[:, -window:], v[:, -window:]
        positions = positions[:, -window:]
    return KVCache(k=k, v=v, pos=jnp.full((B,), S, jnp.int32), positions=positions)


def decode_attention_step(params, x, cache: KVCache, cfg: ModelConfig,
                          window: Optional[int] = None) -> tuple:
    """One-token attention: x (B, 1, d) against the cache; returns (out, cache)."""
    B = x.shape[0]
    dt = x.dtype
    pos = cache.pos                                            # (B,)
    q, k_new, v_new = _project_qkv(params, x, x, cfg, pos[:, None], pos[:, None])
    # slot: ring buffer when windowed, else absolute position
    C = cache.capacity
    slot = (pos % C).astype(jnp.int32)                         # (B,)
    bidx = jnp.arange(B)
    k = cache.k.at[bidx, slot].set(k_new[:, 0].astype(cache.k.dtype))
    v = cache.v.at[bidx, slot].set(v_new[:, 0].astype(cache.v.dtype))
    positions = cache.positions.at[bidx, slot].set(pos)
    k = shard(k, "batch", "seq_kv", "kv_heads", None)
    v = shard(v, "batch", "seq_kv", "kv_heads", None)

    H, hd = q.shape[2], q.shape[3]
    K = k.shape[2]
    G = H // K
    if cfg.use_pallas:
        from ..kernels import ops as kops

        out = kops.decode_attention(q[:, 0], k, v, positions, pos, window=window)
        out = out[:, None]
    else:
        q5 = q.reshape(B, 1, K, G, hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", q5.astype(jnp.float32),
                            k.astype(jnp.float32)) / math.sqrt(hd)     # (B,K,G,1,C)
        valid = (positions >= 0) & (positions <= pos[:, None])
        if window is not None:
            valid &= positions > pos[:, None] - window
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
        out = out.reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    new_cache = KVCache(k=k, v=v, pos=pos + 1, positions=positions)
    return y, new_cache
