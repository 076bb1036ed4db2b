"""Shard_map-native layer primitives (Megatron-JAX style).

Everything in this module runs INSIDE shard_map: parameters arrive as local
shards, activations are replicated across the 'model' axis, and tensor
parallelism is expressed with explicit lax collectives:

  column-parallel in-projections : no communication
  row-parallel out-projections   : lax.psum over 'model'
  vocab-sharded embedding/logits : lax.psum over 'model'

The blocked-attention implementations here are the pure-jnp twins of the
Pallas kernels in repro.kernels (same math, scan-over-KV-tiles online
softmax) so that CPU dry-runs lower to compact HLO with O(s*d) memory.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Static sharding context threaded through the model code."""
    tp: int = 1                   # size of 'model' axis
    dp: int = 1                   # size of 'data' axis
    pods: int = 1                 # size of 'pod' axis (1 = single pod)
    model_axis: str = "model"
    data_axis: str = "data"
    pod_axis: str = "pod"
    fsdp: bool = False            # params sharded over data axis
    seq_shard_cache: bool = False  # decode KV cache sharded over data axis
    seq_parallel: bool = False    # residual stream seq-sharded over model
    remat_groups: int = 0         # nested-remat group count (0 = flat scan)

    @property
    def dp_axes(self) -> tuple:
        return (self.pod_axis, self.data_axis) if self.pods > 1 else (self.data_axis,)


def tp_index(ctx: ShardCtx):
    return lax.axis_index(ctx.model_axis)


def gather_fsdp(ctx: ShardCtx, w: jnp.ndarray, axis: int) -> jnp.ndarray:
    """All-gather an FSDP-sharded weight along ``axis`` (no-op w/o fsdp).
    Backward is automatically psum_scatter (ZeRO-3 gradient flow)."""
    if not ctx.fsdp:
        return w
    return lax.all_gather(w, ctx.data_axis, axis=axis, tiled=True)


def sp_gather(ctx: ShardCtx, h: jnp.ndarray) -> jnp.ndarray:
    """Sequence-parallel entry: all-gather the seq-sharded activations to
    full sequence before TP matmuls (Megatron-SP). No-op without SP."""
    if not ctx.seq_parallel:
        return h
    return lax.all_gather(h, ctx.model_axis, axis=1, tiled=True)


def sp_out(ctx: ShardCtx, y: jnp.ndarray) -> jnp.ndarray:
    """Row-parallel exit: with SP, reduce-scatter the block output back to
    the seq-sharded residual layout (same wire bytes as the psum it
    replaces, 1/tp the activation memory); otherwise psum."""
    if ctx.seq_parallel:
        return lax.psum_scatter(y, ctx.model_axis, scatter_dimension=1,
                                tiled=True)
    return lax.psum(y, ctx.model_axis)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., t, h, hd), pos: (t,) or (b, t)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[..., None].astype(jnp.float32) * freqs        # (..., t, hd/2)
    ang = ang[..., None, :]                                  # broadcast heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------- embedding & loss -------------------------

def embed_lookup(ctx: ShardCtx, emb: jnp.ndarray, ids: jnp.ndarray,
                 vocab: int) -> jnp.ndarray:
    """Vocab-sharded embedding lookup. emb: (V_local, d) local shard."""
    v_local = emb.shape[0]
    lo = tp_index(ctx) * v_local
    local = jnp.clip(ids - lo, 0, v_local - 1)
    x = jnp.take(emb, local, axis=0)
    mask = ((ids >= lo) & (ids < lo + v_local))[..., None]
    x = jnp.where(mask, x, 0).astype(emb.dtype)
    return sp_out(ctx, x)


def lm_loss(ctx: ShardCtx, x: jnp.ndarray, head: jnp.ndarray,
            targets: jnp.ndarray, mask: jnp.ndarray | None = None,
            chunk: int = 1024):
    """Vocab-sharded cross-entropy. x: (b, t, d), head: (d, V_local),
    targets: (b, t) global token ids. Returns mean NLL over local tokens.

    Long sequences are processed in seq chunks under jax.checkpoint so the
    (b, t, V_local) fp32 logits are never live all at once (§Perf:
    memory term)."""
    t = x.shape[1]
    if t > chunk:
        pad = (-t) % chunk
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = x.shape[1] // chunk
        xs = x.reshape(x.shape[0], nc, chunk, -1).transpose(1, 0, 2, 3)
        ts = targets.reshape(targets.shape[0], nc, chunk).transpose(1, 0, 2)
        ms = mask.reshape(mask.shape[0], nc, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def body(acc, ins):
            xc, tc, mc = ins
            nll_mean = lm_loss(ctx, xc, head, tc, mask=mc, chunk=10 ** 9)
            return (acc[0] + nll_mean * jnp.sum(mc), acc[1] + jnp.sum(mc)), None

        (tot, cnt), _ = lax.scan(body, (0.0, 0.0), (xs, ts, ms))
        return tot / jnp.maximum(cnt, 1.0)
    v_local = head.shape[-1]
    logits = (x @ head).astype(jnp.float32)                 # (b, t, Vl)
    # stability shift only — no gradient needs to flow through the max,
    # so stop_gradient BEFORE pmax (pmax has no differentiation rule)
    m = lax.pmax(jnp.max(lax.stop_gradient(logits), axis=-1),
                 ctx.model_axis)                             # (b, t)
    lse = jnp.log(lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                           ctx.model_axis)) + m
    lo = tp_index(ctx) * v_local
    local_t = jnp.clip(targets - lo, 0, v_local - 1)
    tgt_logit = jnp.take_along_axis(logits, local_t[..., None], axis=-1)[..., 0]
    in_shard = (targets >= lo) & (targets < lo + v_local)
    tgt_logit = lax.psum(jnp.where(in_shard, tgt_logit, 0.0), ctx.model_axis)
    nll = lse - tgt_logit
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------ blocked attention -------------------------

NEG_INF = -1e30


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, blk_q: int = 1024,
                      blk_kv: int = 512) -> jnp.ndarray:
    """Online-softmax attention, scan over Q tiles x KV tiles
    (O(blk_q*blk_kv) score memory).  The CPU path, the path for shapes the
    fused kernel does not take (MLA's V head dim, lengths off its tiles;
    ``kernels.ops.flash_attention`` dispatches), and the kernel's oracle.

    q: (b, h, sq, hd), k/v: (b, hkv, skv, hd). GQA-aware."""
    b, h, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]                       # MLA: v head dim may differ
    rep = h // hkv
    scale = hd ** -0.5
    blk_q = min(blk_q, sq)
    blk_kv = min(blk_kv, skv)
    pad_q = (-sq) % blk_q
    pad_kv = (-skv) % blk_kv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, sq, hd) * scale
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if pad_kv:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    nq, nkv = qf.shape[3] // blk_q, kf.shape[2] // blk_kv
    # (nq, b, g, r, blk_q, hd)
    qt = qf.reshape(b, hkv, rep, nq, blk_q, hd).transpose(3, 0, 1, 2, 4, 5)
    kt = kf.reshape(b, hkv, nkv, blk_kv, hd).transpose(2, 0, 1, 3, 4)
    vt = vf.reshape(b, hkv, nkv, blk_kv, hdv).transpose(2, 0, 1, 3, 4)
    shift = skv - sq  # causal alignment at the sequence end

    def q_tile(_, qin):
        qb, qi = qin
        rows = qi * blk_q + jnp.arange(blk_q)

        @jax.checkpoint
        def kv_tile(carry, kin):
            m_prev, l_prev, acc = carry
            kb, vb, ki = kin
            cols = ki * blk_kv + jnp.arange(blk_kv)
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qb, kb)
            keep = cols[None, :] < skv
            if causal:
                keep = keep & (cols[None, :] <= rows[:, None] + shift)
            s = jnp.where(keep, s, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_cur[..., None])
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bgrqk,bgkd->bgrqd", p, vb)
            return (m_cur, l_cur, acc), None

        m0 = jnp.full((b, hkv, rep, blk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, rep, blk_q), jnp.float32)
        a0 = jnp.zeros((b, hkv, rep, blk_q, hdv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_tile, (m0, l0, a0),
                                  (kt, vt, jnp.arange(nkv)))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, out = lax.scan(jax.checkpoint(q_tile), None, (qt, jnp.arange(nq)))
    # (nq, b, g, r, blk_q, hd) -> (b, h, sq, hd)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, rep, nq * blk_q, hdv)
    out = out[:, :, :, :sq].reshape(b, h, sq, hdv)
    return out.astype(q.dtype)


def decode_attention(ctx: ShardCtx, q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """One-token attention over a (possibly sequence-sharded) KV cache.

    q: (b, h, 1, hd); k_cache/v_cache: (b, hkv, S_local, hd); pos: ()
    global number of valid cache entries, or (b,) per-slot counts (the
    continuous-batching engine packs sequences of different lengths into
    one batch). When ctx.seq_shard_cache, the cache's S dim is sharded
    over the data axis and partial softmax stats are merged across it
    (flash-decode)."""
    b, h, _, hd = q.shape
    hkv, s_local = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    scale = hd ** -0.5
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, hd) * scale
    kf = k_cache.astype(jnp.float32)
    # f32 on every backend (TPU's default precision rounds f32 operands
    # to bf16): the paged kernel computes the same f32 attention
    hi = lax.Precision.HIGHEST
    s = jnp.einsum("bgrd,bgkd->bgrk", qf, kf, precision=hi)
    if ctx.seq_shard_cache:
        offset = lax.axis_index(ctx.data_axis) * s_local
    else:
        offset = 0
    pos = jnp.asarray(pos)
    idx = offset + jnp.arange(s_local)
    if pos.ndim:
        valid = (idx[None, :] < pos[:, None])[:, None, None, :]
    else:
        valid = (idx < pos)[None, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if ctx.seq_shard_cache:
        m = lax.pmax(m, ctx.data_axis)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bgrk,bgkd->bgrd", p, v_cache.astype(jnp.float32),
                     precision=hi)
    if ctx.seq_shard_cache:
        l = lax.psum(l, ctx.data_axis)
        acc = lax.psum(acc, ctx.data_axis)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, 1, hd).astype(q.dtype)


# ------------------------------- MLP --------------------------------

def swiglu_mlp(ctx: ShardCtx, x: jnp.ndarray, w_gate, w_up, w_down):
    """Column/row-parallel SwiGLU. w_gate/w_up: (d, ff_local) local shards,
    w_down: (ff_local, d). Ends with psum over the model axis."""
    g = x @ gather_fsdp(ctx, w_gate, 0)
    u = x @ gather_fsdp(ctx, w_up, 0)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = h @ gather_fsdp(ctx, w_down, 1)
    return lax.psum(out, ctx.model_axis)


def update_cache(cache: jnp.ndarray, new: jnp.ndarray, pos,
                 ctx: ShardCtx) -> jnp.ndarray:
    """Write one decode step's K or V into the cache at global position
    ``pos``. cache: (b, hkv, S_local, hd), new: (b, hkv, 1, hd)."""
    if ctx.seq_shard_cache:
        s_local = cache.shape[2]
        owner = pos // s_local
        local_pos = pos - owner * s_local
        updated = lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, 0, local_pos, 0))
        mine = lax.axis_index(ctx.data_axis) == owner
        return jnp.where(mine, updated, cache)
    return lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                    (0, 0, pos, 0))


# -------------------------- paged KV cache ---------------------------

def paged_update_cache(pool: jnp.ndarray, new: jnp.ndarray, page_ids,
                       offsets) -> jnp.ndarray:
    """Write one decode step's K or V for a packed slot batch into a paged
    pool.  pool: (P, hkv, page, hd) physical pages shared by every slot;
    new: (b, hkv, 1, hd); page_ids/offsets: (b,) each slot's target page
    and in-page offset.  Inactive slot rows point at the reserved null
    page 0, whose contents are never read as valid."""
    return pool.at[page_ids, :, offsets, :].set(
        new[:, :, 0, :].astype(pool.dtype))


def paged_gather(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize each slot's pages as a contiguous (b, hkv, nb*page, hd)
    KV view.  pool: (P, hkv, page, hd); page_table: (b, nb) page ids in
    logical-block order.  Table entries beyond a slot's allocation hit the
    null page and are masked out by decode_attention's validity test."""
    b, nb = page_table.shape
    _, hkv, ps, hd = pool.shape
    pages = jnp.take(pool, page_table, axis=0)       # (b, nb, hkv, ps, hd)
    return pages.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * ps, hd)
