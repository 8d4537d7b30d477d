"""Mixture-of-Experts FFN with sort-based token dispatch.

Dispatch is capacity-bounded and sort-based (Megablocks-style, adapted to
XLA/TPU): tokens are argsorted by expert id, ranked within their expert, and
scattered into dense (E, C, d) buffers, so expert compute is plain batched
einsum on MXU-aligned shapes and the compiled FLOPs reflect *active* experts
only (top-k), keeping the roofline's MoE accounting honest.  Tokens beyond
capacity are dropped (standard GShard semantics, capacity_factor 1.25).

Supports Arctic's "dense residual": a standard MLP running in parallel with
the MoE, summed at the output.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import dense_init, init_mlp, mlp, shard


def init_moe(key, cfg: ModelConfig) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    pdt = cfg.jparam_dtype
    p = {
        "router": dense_init(ks[0], (d, E), pdt),
        "wi": dense_init(ks[1], (E, d, f), pdt, fan_in=d),
        "wg": dense_init(ks[2], (E, d, f), pdt, fan_in=d),
        "wo": dense_init(ks[3], (E, f, d), pdt, fan_in=f),
    }
    if cfg.dense_residual:
        p["dense"] = init_mlp(ks[4], cfg, d_ff=cfg.d_ff)
    return p


def _moe_groups(N: int, E: int, B: int) -> int:
    """Number of dispatch groups: one per data shard when it divides the
    batch (locality by construction — sort/scatter never cross shards),
    clamped so each group still feeds every expert a reasonable slice."""
    am = jax.sharding.get_abstract_mesh()
    dsize = 1
    if not am.empty:
        for a in ("pod", "data"):
            if a in am.axis_names:
                dsize *= am.shape[a]
    G = dsize
    while G > 1 and (B % G or (N // G) < 2 * E):
        G //= 2
    return max(G, 1)


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig) -> tuple:
    """x: (B, S, d) -> (y, aux_loss).

    Grouped local dispatch: tokens are split into G groups, one per data
    shard (read off the abstract mesh at trace time), and ALL dispatch
    machinery is per-group — batched argsort rows, searchsorted counts,
    take_along_axis gathers — so nothing crosses shards.  The only scatter is
    the capacity-buffer fill, with group-major *sorted unique* indices.  The
    combine is scatter-free: each (token, choice) pair gathers its expert
    output back through the inverse sort permutation.  Expert einsums carry
    an explicit G dim sharded on 'data' with experts (or the expert FFN dim)
    sharded on 'model': compiled FLOPs are active-only with no data-axis
    redundancy.  Dropping is per-group (standard for dropping MoE)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    N = B * S
    dt = x.dtype
    G = _moe_groups(N, E, B)
    n = N // G

    flat = x.reshape(G, n, d)
    flat = shard(flat, "batch", None, "d_model")

    am = jax.sharding.get_abstract_mesh()
    data_axes = tuple(a for a in ("pod", "data")
                      if not am.empty and a in am.axis_names)
    dsize = 1
    for a in data_axes:
        dsize *= am.shape[a]
    if cfg.moe_shard_map and data_axes and dsize > 1 and G % dsize == 0:
        # Manual over the data axes: the dispatch below becomes provably
        # shard-local (GSPMD cannot insert conservative collectives around
        # the scatter/gathers); 'model' stays auto for the expert einsums.
        from jax.sharding import PartitionSpec as P

        spec_g = P(data_axes if len(data_axes) > 1 else data_axes[0])
        # NOTE(perf, TPU): doing the boundary grad-psum in bf16 would halve
        # its wire bytes, but XLA:CPU crashes compiling bf16 all-reduce
        # ("Invalid binary instruction opcode copy" in AllReducePromotion).
        # bf16 params are therefore staged through f32 before capture so the
        # psum stays f32 — one extra per-layer cast (~0.4s memory-term for
        # arctic) instead of a 16x collective blowup.  See EXPERIMENTS.md §Perf.
        logical = {"wi": ("experts", None, None), "wg": ("experts", None, None),
                   "wo": ("experts", None, None), "router": (None, None)}
        cap = {}
        for kk, ax in logical.items():
            w = params[kk]
            if cfg.fsdp_params:
                # undo the data-axis shard (per-layer FSDP all-gather); the
                # constraint's transpose reduce-scatters the grads back
                w = shard(w, *ax)
            if w.dtype == jnp.bfloat16:
                w = w.astype(jnp.float32)   # f32 boundary psum (XLA:CPU bug)
            cap[kk] = w

        def _local(fl):
            y, aux = _grouped_dispatch(cap, fl, cfg)
            return y, jax.lax.psum(aux, data_axes) / dsize

        local = jax.shard_map(_local, in_specs=(spec_g,),
                              out_specs=(spec_g, P()),
                              axis_names=set(data_axes), check_vma=False)
        y, aux = local(flat)
    else:
        y, aux = _grouped_dispatch(params, flat, cfg)
    y = shard(y, "batch", None, "d_model")
    y = y.reshape(B, S, d)

    if cfg.dense_residual:
        y = y + mlp(params["dense"], x, cfg)
    return shard(y, "batch", "seq", "d_model"), aux


def _grouped_dispatch(params, flat, cfg: ModelConfig) -> tuple:
    """Dispatch + expert compute for (G_local, n, d) token groups.  All ops
    are row-local; safe to run under data-manual shard_map."""
    G, n, d = flat.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = flat.dtype
    nk = n * k
    C = max(1, int(math.ceil(n * k / E * cfg.capacity_factor)))

    logits = jnp.einsum("gnd,de->gne", flat.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_logits, top_ids = jax.lax.top_k(logits, k)                  # (G, n, k)
    weights = jax.nn.softmax(top_logits, axis=-1).astype(dt)        # mixtral convention

    # Load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs_full, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(top_ids[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # ---- per-group sort-based dispatch (all row-local ops) -----------------
    eids = top_ids.reshape(G, nk)
    token_of = jnp.broadcast_to(
        jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)[None], (G, nk))
    order = jnp.argsort(eids, axis=-1, stable=True)                  # (G, nk)
    e_sorted = jnp.take_along_axis(eids, order, axis=-1)
    tok_sorted = jnp.take_along_axis(token_of, order, axis=-1)
    # counts per expert from the sorted rows (no scatter): binary search
    bounds = jnp.arange(E + 1, dtype=jnp.int32)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, bounds, side="left"))(
        e_sorted)                                                    # (G, E+1)
    offsets = starts[:, :-1]                                         # (G, E)
    rank = jnp.arange(nk, dtype=jnp.int32)[None] - \
        jnp.take_along_axis(offsets, e_sorted, axis=-1)              # (G, nk)
    keep = rank < C
    r_idx = jnp.minimum(rank, C - 1)

    gathered = jnp.take_along_axis(flat, tok_sorted[..., None], axis=1)
    gathered = gathered * keep[..., None].astype(dt)                 # (G, nk, d)

    # one scatter: group-major flattened, indices sorted & unique
    tgt = e_sorted * C + r_idx                                       # (G, nk)
    gidx = (jnp.arange(G, dtype=jnp.int32)[:, None] * (E * C) + tgt).reshape(-1)
    buf = jnp.zeros((G * E * C, d), dt)
    buf = buf.at[gidx].add(gathered.reshape(G * nk, d),
                           indices_are_sorted=True)
    buf = buf.reshape(G, E, C, d)
    buf = shard(buf, None, "experts", None, "d_model")

    # ---- expert compute (explicit G dim) -----------------------------------
    h = jnp.einsum("gecd,edf->gecf", buf, params["wi"].astype(dt))
    g = jnp.einsum("gecd,edf->gecf", buf, params["wg"].astype(dt))
    h = jax.nn.silu(g) * h
    h = shard(h, None, "experts", None, "ff")
    out = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(dt))
    out = shard(out, None, "experts", None, "d_model")

    # ---- scatter-free combine: inverse-permutation gathers ------------------
    inv_order = jnp.argsort(order, axis=-1)                          # (G, nk)
    loc_sorted = e_sorted * C + r_idx                                # (G, nk)
    loc = jnp.take_along_axis(loc_sorted, inv_order, axis=-1)        # pair order
    keep_pair = jnp.take_along_axis(keep, inv_order, axis=-1)
    out_flat = out.reshape(G, E * C, d)
    back = jnp.take_along_axis(out_flat, loc[..., None], axis=1)     # (G, nk, d)
    back = back * (weights.reshape(G, nk) * keep_pair.astype(dt))[..., None]
    y = back.reshape(G, n, k, d).sum(axis=2)                         # (G, n, d)
    return y, aux


def moe_ffn_tokens(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Decode-friendly MoE for small N (B tokens): per-token expert gather.

    For single-token decode, dispatch-sort machinery is overkill; compute the
    k selected experts per token by gathering their weights (N*k small)."""
    B, S, d = x.shape
    N = B * S
    k = cfg.top_k
    dt = x.dtype
    flat = x.reshape(N, d)
    logits = jnp.einsum("nd,de->ne", flat.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    top_logits, top_ids = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_logits, axis=-1).astype(dt)        # (N, k)
    wi = params["wi"].astype(dt)[top_ids]                            # (N, k, d, f)
    wg = params["wg"].astype(dt)[top_ids]
    wo = params["wo"].astype(dt)[top_ids]                            # (N, k, f, d)
    h = jnp.einsum("nd,nkdf->nkf", flat, wi)
    g = jnp.einsum("nd,nkdf->nkf", flat, wg)
    h = jax.nn.silu(g) * h
    out = jnp.einsum("nkf,nkfd->nkd", h, wo)
    y = jnp.einsum("nkd,nk->nd", out, weights).reshape(B, S, d)
    if cfg.dense_residual:
        y = y + mlp(params["dense"], x, cfg)
    return y
