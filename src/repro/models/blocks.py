"""Transformer / SSM / MoE blocks, shard_map-native.

All functions take LOCAL parameter shards and activations replicated over
the 'model' axis; each block ends with exactly one lax.psum over 'model'
(Megatron row-parallel pattern). Heads are padded to a multiple of the TP
degree at init time (zero-weight pad heads: wo pad rows are zero so the
psum is unaffected); KV heads with kv < tp are replicated per shard so that
shard m holds the KV group serving its query heads.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kernel_ops
from .config import ModelConfig
from .layers import (NEG_INF, ShardCtx, decode_attention,
                     embed_lookup, gather_fsdp, paged_gather,
                     paged_update_cache, rmsnorm, rope, sp_gather, sp_out,
                     swiglu_mlp, update_cache)


def _heads_local(h: int, tp: int) -> int:
    """Query heads per shard after padding h up to a multiple of tp."""
    return max(1, -(-h // tp))


def _kv_local(kv: int, tp: int) -> int:
    """KV heads per shard (>=1; kv < tp means replication across shards)."""
    return max(1, kv // tp)


def _qk_headnorm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Per-head RMS norm (qwen3/chameleon qk_norm). x: (..., h, hd)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + 1e-6)).astype(x.dtype) * w


# ============================ GQA attention ============================

def _gqa_qkv(ctx: ShardCtx, cfg: ModelConfig, p, x, pos):
    """Shared self-attention q/k/v projection + qk-norm + RoPE.  The
    contiguous decode path and the paged continuous-batching path both go
    through this, so their per-token math stays bit-identical.  pos: (t,)
    shared positions or (b, t) per-slot positions (rope handles both)."""
    h = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = h.shape
    hl = p["wq"].shape[-1] // cfg.hd
    kvl = p["wk"].shape[-1] // cfg.hd
    q = (h @ gather_fsdp(ctx, p["wq"], 0)).reshape(b, t, hl, cfg.hd)
    k = (h @ gather_fsdp(ctx, p["wk"], 0)).reshape(b, t, kvl, cfg.hd)
    v = (h @ gather_fsdp(ctx, p["wv"], 0)).reshape(b, t, kvl, cfg.hd)
    if cfg.qk_norm:
        q = _qk_headnorm(q, p["q_norm"])
        k = _qk_headnorm(k, p["k_norm"])
    if pos is not None:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def gqa_attention(ctx: ShardCtx, cfg: ModelConfig, p, x, pos,
                  cache=None, cache_pos=None, kv_ext=None, causal=True):
    """p: layer params dict. x: (b, t, d). pos: (t,) positions for RoPE.

    cache=(k,v) enables decode mode (t == 1). kv_ext=(k,v) enables
    cross-attention (whisper decoder). Returns (out, new_cache)."""
    if kv_ext is None:
        q, k, v = _gqa_qkv(ctx, cfg, p, x, pos)
    else:
        h = sp_gather(ctx, rmsnorm(x, p["norm"]))
        hl = p["wq"].shape[-1] // cfg.hd
        q = (h @ gather_fsdp(ctx, p["wq"], 0)).reshape(
            *h.shape[:2], hl, cfg.hd)
        k, v = kv_ext
        if cfg.qk_norm:
            q = _qk_headnorm(q, p["q_norm"])
    b, t, hl = q.shape[:3]
    q = q.transpose(0, 2, 1, 3)                      # (b, hl, t, hd)
    new_cache = None
    if cache is not None and kv_ext is None:
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        kc = update_cache(cache["k"], k, cache_pos, ctx)
        vc = update_cache(cache["v"], v, cache_pos, ctx)
        new_cache = {"k": kc, "v": vc}
        attn = decode_attention(ctx, q, kc, vc, cache_pos + 1)
    else:
        if kv_ext is None:
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
            new_cache = {"k": k, "v": v}   # collected by prefill, DCE'd in train
        attn = kernel_ops.flash_attention(q, k, v, causal=causal)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, t, hl * cfg.hd)
    out = attn @ gather_fsdp(ctx, p["wo"], 1)
    return sp_out(ctx, out), new_cache


def gqa_decode_paged(ctx: ShardCtx, cfg: ModelConfig, p, x, lengths,
                     pool_kv, page_table, backend: str = "gather"):
    """One paged decode step of GQA self-attention over a packed slot
    batch (continuous batching).  x: (b, 1, d) each slot's pending token;
    lengths: (b,) tokens already cached per slot (the new token's
    position); pool_kv: {"k","v"} physical page pools (P, hkv_local,
    page, hd); page_table: (b, nb) per-slot page ids.  Returns
    (out, new_pool_kv) — the same per-token math as the contiguous
    gqa_attention decode branch, so outputs match it bit-exactly.

    ``backend`` is ServeConfig.decode_backend: 'gather' materializes each
    slot's pages contiguous (paged_gather) before decode_attention;
    'paged' attends over the pool in place through the Pallas kernel
    (kernels.paged_attention) where it compiles (TPU, or forced in
    tests) and keeps the gather path as the bit-exact XLA fallback."""
    from ..kernels import paged_attention as paged_kernel
    ps = pool_kv["k"].shape[2]
    q, k, v = _gqa_qkv(ctx, cfg, p, x, lengths[:, None])
    q = q.transpose(0, 2, 1, 3)                      # (b, hl, 1, hd)
    k = k.transpose(0, 2, 1, 3)                      # (b, kvl, 1, hd)
    v = v.transpose(0, 2, 1, 3)
    page_ids = jnp.take_along_axis(page_table, (lengths // ps)[:, None],
                                   axis=1)[:, 0]
    kp = paged_update_cache(pool_kv["k"], k, page_ids, lengths % ps)
    vp = paged_update_cache(pool_kv["v"], v, page_ids, lengths % ps)
    if backend == "paged" and paged_kernel.use_kernel():
        attn = paged_kernel.paged_attention(q, kp, vp, page_table,
                                            lengths + 1)
    else:
        attn = decode_attention(ctx, q, paged_gather(kp, page_table),
                                paged_gather(vp, page_table), lengths + 1)
    b, hl = q.shape[:2]
    attn = attn.transpose(0, 2, 1, 3).reshape(b, 1, hl * cfg.hd)
    out = attn @ gather_fsdp(ctx, p["wo"], 1)
    return sp_out(ctx, out), {"k": kp, "v": vp}


# ========================= MLA (deepseek-v3) ==========================

def mla_attention(ctx: ShardCtx, cfg: ModelConfig, p, x, pos,
                  cache=None, cache_pos=None):
    """Multi-head Latent Attention. Train path materializes per-head K/V
    from the compressed kv; decode path uses the absorbed formulation over
    the compressed cache (head-shared, optionally int8-quantized)."""
    hd, rd, kvr = cfg.hd, cfg.qk_rope_dim, cfg.kv_lora_rank
    h = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = h.shape
    hl = p["wq_b"].shape[-1] // (hd + rd)
    # --- queries ---
    cq = rmsnorm(h @ gather_fsdp(ctx, p["wq_a"], 0), p["q_norm"])
    q = (cq @ p["wq_b"]).reshape(b, t, hl, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)
    # --- compressed kv ---
    ckv_full = h @ gather_fsdp(ctx, p["wkv_a"], 0)     # (b, t, kvr + rd)
    ckv = rmsnorm(ckv_full[..., :kvr], p["kv_norm"])
    k_rope = rope(ckv_full[..., None, kvr:], pos, cfg.rope_theta)  # (b,t,1,rd)

    if cache is None:
        kv = (ckv @ p["wkv_b"]).reshape(b, t, hl, 2 * hd)
        k_nope, v = kv[..., :hd], kv[..., hd:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, hl, rd))],
                            axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        attn = kernel_ops.flash_attention(qf.transpose(0, 2, 1, 3),
                                          k.transpose(0, 2, 1, 3),
                                          v.transpose(0, 2, 1, 3))
        attn = attn.transpose(0, 2, 1, 3).reshape(b, t, hl * hd)
        out = sp_out(ctx, attn @ gather_fsdp(ctx, p["wo"], 1))
        # quantized compressed cache, collected by prefill (DCE'd in train)
        sc = jnp.max(jnp.abs(ckv), axis=-1, keepdims=True) / 127.0 + 1e-8
        new_cache = {"ckv": jnp.round(ckv / sc).astype(jnp.int8),
                     "scale": sc.astype(jnp.float32),
                     "krope": k_rope[:, :, 0]}
        return out, new_cache

    # ---- absorbed decode over the compressed cache ----
    wkv_b = p["wkv_b"].reshape(kvr, hl, 2 * hd)
    wk, wv = wkv_b[..., :hd], wkv_b[..., hd:]
    # absorb K up-projection into the query
    q_c = jnp.einsum("bthd,rhd->bthr", q_nope, wk)     # (b, t, hl, kvr)
    # quantized cache update (int8 + per-token scale)
    ckv_t = ckv[:, 0]                                   # (b, kvr) t == 1
    scale = jnp.max(jnp.abs(ckv_t), axis=-1, keepdims=True) / 127.0 + 1e-8
    ckv_q = jnp.round(ckv_t / scale).astype(jnp.int8)
    c_cache = lax.dynamic_update_slice(
        cache["ckv"], ckv_q[:, None], (0, cache_pos, 0))
    s_cache = lax.dynamic_update_slice(
        cache["scale"], scale.astype(jnp.float32)[:, None], (0, cache_pos, 0))
    r_cache = lax.dynamic_update_slice(
        cache["krope"], k_rope[:, :, 0].astype(cache["krope"].dtype),
        (0, cache_pos, 0))
    new_cache = {"ckv": c_cache, "scale": s_cache, "krope": r_cache}
    cdeq = c_cache.astype(jnp.float32) * s_cache       # (b, S, kvr)
    s_nope = jnp.einsum("bthr,bsr->bths", q_c.astype(jnp.float32), cdeq)
    s_rope = jnp.einsum("bthd,bsd->bths", q_rope.astype(jnp.float32),
                        r_cache.astype(jnp.float32))
    s = (s_nope + s_rope) * ((hd + rd) ** -0.5)
    valid = jnp.arange(c_cache.shape[1]) <= cache_pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bths,bsr->bthr", w, cdeq)        # compressed-space out
    attn = jnp.einsum("bthr,rhd->bthd", o_c, wv.astype(jnp.float32))
    attn = attn.astype(x.dtype).reshape(b, t, hl * hd)
    out = attn @ gather_fsdp(ctx, p["wo"], 1)
    return lax.psum(out, ctx.model_axis), new_cache


# ================================ MoE =================================

def moe_block(ctx: ShardCtx, cfg: ModelConfig, p, x):
    """Top-k routed experts, expert-parallel over the 'model' axis with
    expert-side top-C token selection (capacity-bounded, no all_to_all:
    activations are TP-replicated so each shard runs its local experts).
    p: router (d, E_local), w_gate/w_up (El, d, ffe), w_down (El, ffe, d),
    optional shared expert (d, ff_sh_local)."""
    h = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = h.shape
    xt = h.reshape(b * t, d)
    n_tok = b * t
    logits_l = (xt @ p["router"]).astype(jnp.float32)        # (T, El)
    logits = lax.all_gather(logits_l, ctx.model_axis, axis=1, tiled=True)
    gates = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    top_g, top_e = lax.top_k(gates, cfg.top_k)
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    full = jnp.zeros_like(gates).at[jnp.arange(n_tok)[:, None], top_e].set(top_g)
    el = p["router"].shape[-1]
    e_lo = lax.axis_index(ctx.model_axis) * el
    local_gates = lax.dynamic_slice(full, (0, e_lo), (n_tok, el))  # (T, El)
    cap = int(n_tok * cfg.top_k / cfg.n_experts * cfg.capacity_factor) + 1
    cap = min(cap, n_tok)
    # expert-side top-C token selection
    g_sel, idx = lax.top_k(local_gates.T, cap)                # (El, C)
    xe = jnp.take(xt, idx.reshape(-1), axis=0).reshape(el, cap, d)
    wg = gather_fsdp(ctx, p["w_gate"], 1)
    wu = gather_fsdp(ctx, p["w_up"], 1)
    wd = gather_fsdp(ctx, p["w_down"], 2)
    gh = jnp.einsum("ecd,edf->ecf", xe, wg)
    uh = jnp.einsum("ecd,edf->ecf", xe, wu)
    hh = jax.nn.silu(gh.astype(jnp.float32)).astype(x.dtype) * uh
    ye = jnp.einsum("ecf,efd->ecd", hh, wd)
    ye = ye * g_sel[..., None].astype(ye.dtype)
    out = jnp.zeros((n_tok, d), ye.dtype).at[idx.reshape(-1)].add(
        ye.reshape(-1, d))
    if "sh_gate" in p:  # shared experts (deepseek): ordinary TP mlp, no norm
        g = xt @ gather_fsdp(ctx, p["sh_gate"], 0)
        u = xt @ gather_fsdp(ctx, p["sh_up"], 0)
        out = out + (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
                     ) @ gather_fsdp(ctx, p["sh_down"], 1)
    out = sp_out(ctx, out.reshape(b, t, d))
    # auxiliary load-balance loss (switch-style)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(full > 0, axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return out, aux


# =============================== Mamba2 ===============================

def _ssd_chunk_scan(xh, dt, a_log, bmat, cmat, chunk: int):
    """SSD chunked scan (Mamba-2). xh: (b, t, nh, hp); dt: (b, t, nh)
    (post-softplus); a_log: (nh,) (negative); bmat/cmat: (b, t, N).
    Returns y: (b, t, nh, hp) and final state (b, nh, hp, N)."""
    b, t, nh, hp = xh.shape
    n = bmat.shape[-1]
    pad = (-t) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    tc = xh.shape[1]
    nc = tc // chunk
    xc = xh.reshape(b, nc, chunk, nh, hp)
    dtc = dt.reshape(b, nc, chunk, nh)
    bc = bmat.reshape(b, nc, chunk, n)
    cc = cmat.reshape(b, nc, chunk, n)
    da = dtc * a_log[None, None, None, :]               # (b, nc, Q, nh) <= 0
    cum = jnp.cumsum(da, axis=2)

    def chunk_body(state, ins):
        xq, dq, bq, cq, daq, cumq = ins                 # leading axis = chunks
        # intra-chunk: y[i] = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
        rel = cumq[:, :, None, :] - cumq[:, None, :, :]  # (b, Q, Q, nh)
        iq = jnp.arange(chunk)
        maskq = iq[:, None] >= iq[None, :]
        dec = jnp.where(maskq[None, :, :, None], jnp.exp(rel), 0.0)
        cb = jnp.einsum("bin,bjn->bij", cq, bq)          # (b, Q, Q)
        w = cb[..., None] * dec * dq[:, None, :, :]      # (b, Q, Q, nh)
        y_intra = jnp.einsum("bijh,bjhp->bihp", w, xq)
        # inter-chunk: y[i] += (C_i . S_prev) * exp(cum_i)
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", cq, state, jnp.exp(cumq))
        # state update: S = S*exp(cum_last) + sum_j exp(cum_last - cum_j) dt_j B_j x_j^T
        last = cumq[:, -1:, :]                            # (b, 1, nh)
        wj = jnp.exp(last - cumq) * dq                    # (b, Q, nh)
        decay_last = jnp.exp(cumq[:, -1, :])              # (b, nh)
        s_chunk = jnp.einsum("bjh,bjn,bjhp->bhpn", wj, bq, xq)
        state = state * decay_last[:, :, None, None] + s_chunk
        return state, y_intra + y_inter

    state0 = jnp.zeros((b, nh, hp, n), jnp.float32)
    ins = tuple(z.transpose(1, 0, *range(2, z.ndim))
                for z in (xc, dtc, bc, cc, da, cum))
    state, yc = lax.scan(chunk_body, state0, ins)
    y = yc.transpose(1, 0, 2, 3, 4).reshape(b, tc, nh, hp)[:, :t]
    return y, state


def mamba2_block(ctx: ShardCtx, cfg: ModelConfig, p, x, state=None,
                 chunk: int = 128):
    """Mamba-2 (SSD) block, heads sharded over 'model'. Depthwise causal
    conv (k=4) on x/B/C paths. state: (b, nh_local, hp, N) for decode."""
    h = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = h.shape
    n = cfg.ssm_state
    di_l = p["w_x"].shape[-1]
    nh_l = p["a_log"].shape[0]
    hp = di_l // nh_l
    xs = h @ gather_fsdp(ctx, p["w_x"], 0)              # (b, t, di_l)
    z = h @ gather_fsdp(ctx, p["w_z"], 0)
    bc = h @ gather_fsdp(ctx, p["w_bc"], 0)              # (b, t, 2N)
    dt_raw = h @ p["w_dt"]   # (b, t, nh_l); w_dt is not FSDP-sharded

    def dconv(sig, w, prev=None):
        # causal depthwise conv, kernel k. sig: (b, t, c), w: (k, c)
        k = w.shape[0]
        if prev is None:
            padded = jnp.pad(sig, ((0, 0), (k - 1, 0), (0, 0)))
        else:
            padded = jnp.concatenate([prev, sig], axis=1)
        out = sum(padded[:, i:i + sig.shape[1]] * w[i] for i in range(k))
        return out, padded[:, -(k - 1):]

    if state is not None:
        xs, cs_x = dconv(xs, p["conv_x"], state["conv_x"])
        bc, cs_bc = dconv(bc, p["conv_bc"], state["conv_bc"])
    else:
        xs, cs_x = dconv(xs, p["conv_x"])
        bc, cs_bc = dconv(bc, p["conv_bc"])
    conv_state = {"conv_x": cs_x.astype(jnp.float32),
                  "conv_bc": cs_bc.astype(jnp.float32)}
    xs = jax.nn.silu(xs.astype(jnp.float32))
    bc = jax.nn.silu(bc.astype(jnp.float32))
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    xh = xs.reshape(b, t, nh_l, hp)
    a_log = -jnp.exp(p["a_log"].astype(jnp.float32))

    if state is None:
        y, new_s = _ssd_chunk_scan(xh, dt, a_log, bmat, cmat, chunk)
        new_state = {"ssm": new_s, **conv_state}  # prefill final state
    else:
        # single-step recurrence
        s_prev = state["ssm"]
        da = jnp.exp(dt[:, 0] * a_log[None, :])          # (b, nh)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0], bmat[:, 0], xh[:, 0])
        s_new = s_prev * da[..., None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", cmat[:, 0], s_new)[:, None]
        new_state = {"ssm": s_new, **conv_state}
        y = y.reshape(b, 1, nh_l, hp)
    y = y + xh * p["d_skip"][None, None, :, None]
    y = (y.reshape(b, t, di_l) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ gather_fsdp(ctx, p["w_out"], 1)
    return sp_out(ctx, out), new_state


# =============================== xLSTM ================================

def mlstm_block(ctx: ShardCtx, cfg: ModelConfig, p, x, state=None,
                chunk: int = 128):
    """mLSTM (matrix memory) block, chunkwise-parallel, heads sharded.

    Linear-attention-like with exponential input gate and sigmoid forget
    gate accumulated in log space (float32, clipped)."""
    h = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = h.shape
    di_l = p["w_q"].shape[-1]
    nh_l = p["w_if"].shape[-1] // 2
    hp = di_l // nh_l
    q = (h @ gather_fsdp(ctx, p["w_q"], 0)).reshape(b, t, nh_l, hp)
    k = (h @ gather_fsdp(ctx, p["w_k"], 0)).reshape(b, t, nh_l, hp)
    v = (h @ gather_fsdp(ctx, p["w_v"], 0)).reshape(b, t, nh_l, hp)
    z = h @ gather_fsdp(ctx, p["w_z"], 0)
    gif = h @ gather_fsdp(ctx, p["w_if"], 0)             # (b, t, 2*nh_l)
    i_raw = gif[..., :nh_l].astype(jnp.float32)
    f_raw = gif[..., nh_l:].astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(f_raw)                     # <= 0
    qf = q.astype(jnp.float32) * hp ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    if state is None:
        # chunkwise: identical skeleton to SSD with per-head scalar decay
        pad = (-t) % chunk
        if pad:
            qf, kf, vf = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (qf, kf, vf))
            log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))
            i_raw = jnp.pad(i_raw, ((0, 0), (0, pad), (0, 0)), constant_values=-30.)
        tc = qf.shape[1]
        nc = tc // chunk
        shp = (b, nc, chunk, nh_l)
        qc = qf.reshape(b, nc, chunk, nh_l, hp)
        kc = kf.reshape(b, nc, chunk, nh_l, hp)
        vc = vf.reshape(b, nc, chunk, nh_l, hp)
        fc = jnp.clip(log_f.reshape(shp), -30.0, 0.0)
        ic = jnp.exp(jnp.clip(i_raw.reshape(shp), -30.0, 10.0))
        cum = jnp.cumsum(fc, axis=2)

        def body(carry, ins):
            c_state, n_state = carry                     # (b,nh,hp,hp),(b,nh,hp)
            qq, kk, vv, cumq, ii = ins
            rel = cumq[:, :, None, :] - cumq[:, None, :, :]
            iq = jnp.arange(chunk)
            maskq = iq[:, None] >= iq[None, :]
            dec = jnp.where(maskq[None, :, :, None], jnp.exp(rel), 0.0)
            w = jnp.einsum("bihp,bjhp->bijh", qq, kk) * dec * ii[:, None]
            y_intra = jnp.einsum("bijh,bjhp->bihp", w, vv)
            n_intra = jnp.einsum("bijh,bjhp->bihp", w, jnp.ones_like(vv[..., :1]))
            ed = jnp.exp(cumq)                           # (b, Q, nh)
            y_inter = jnp.einsum("bihp,bhpv,bih->bihv", qq, c_state, ed)
            n_inter = jnp.einsum("bihp,bhp,bih->bih", qq, n_state, ed)[..., None]
            last = jnp.exp(cumq[:, -1, :])               # (b, nh)
            wj = jnp.exp(cumq[:, -1:, :] - cumq) * ii    # (b, Q, nh)
            c_state = (c_state * last[:, :, None, None]
                       + jnp.einsum("bjh,bjhp,bjhv->bhpv", wj, kk, vv))
            n_state = (n_state * last[:, :, None]
                       + jnp.einsum("bjh,bjhp->bhp", wj, kk))
            denom = jnp.maximum(jnp.abs(n_intra + n_inter), 1.0)
            return (c_state, n_state), (y_intra + y_inter) / denom

        c0 = jnp.zeros((b, nh_l, hp, hp), jnp.float32)
        n0 = jnp.zeros((b, nh_l, hp), jnp.float32)
        ins = tuple(a.transpose(1, 0, *range(2, a.ndim))
                    for a in (qc, kc, vc, cum, ic))
        (cS, nS), yc = lax.scan(body, (c0, n0), ins)
        y = yc.transpose(1, 0, 2, 3, 4).reshape(b, tc, nh_l, hp)[:, :t]
        new_state = {"c": cS, "n": nS}  # prefill final state
    else:
        cS, nS = state["c"], state["n"]
        f1 = jnp.exp(jnp.clip(log_f[:, 0], -30.0, 0.0))
        i1 = jnp.exp(jnp.clip(i_raw[:, 0], -30.0, 10.0))
        cS = cS * f1[..., None, None] + i1[..., None, None] * jnp.einsum(
            "bhp,bhv->bhpv", kf[:, 0], vf[:, 0])
        nS = nS * f1[..., None] + i1[..., None] * kf[:, 0]
        num = jnp.einsum("bhp,bhpv->bhv", qf[:, 0], cS)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", qf[:, 0], nS)), 1.0)
        y = (num / den[..., None])[:, None]
        new_state = {"c": cS, "n": nS}
    y = (y.reshape(b, t, di_l) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ gather_fsdp(ctx, p["w_out"], 1)
    return sp_out(ctx, out), new_state


def slstm_block(ctx: ShardCtx, cfg: ModelConfig, p, x, state=None):
    """sLSTM (scalar memory, exponential gating with stabilizer), heads
    sharded over 'model'; sequential lax.scan over time."""
    hn = sp_gather(ctx, rmsnorm(x, p["norm"]))
    b, t, d = hn.shape
    di_l = p["w_in"].shape[-1] // 4
    nh_l = p["r"].shape[0]
    hp = di_l // nh_l
    gates_x = (hn @ gather_fsdp(ctx, p["w_in"], 0)).astype(jnp.float32)

    def step(carry, gx):
        hprev, c, nrm, m = carry                          # (b, nh, hp) each, m (b, nh,hp)
        rec = jnp.einsum("bhp,hpq->bhq", hprev, p["r"].astype(jnp.float32))
        g = gx.reshape(b, nh_l, 4 * hp) + jnp.concatenate([rec] * 4, axis=-1)
        zi, ii, ff, oo = jnp.split(g, 4, axis=-1)
        zt = jnp.tanh(zi)
        log_f = jax.nn.log_sigmoid(ff)
        m_new = jnp.maximum(log_f + m, ii)
        i_p = jnp.exp(ii - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        c = f_p * c + i_p * zt
        nrm = f_p * nrm + i_p
        hcur = jax.nn.sigmoid(oo) * c / jnp.maximum(nrm, 1.0)
        return (hcur, c, nrm, m_new), hcur

    zeros = jnp.zeros((b, nh_l, hp), jnp.float32)
    if state is not None:
        carry0 = (state["h"], state["c"], state["n"], state["m"])
    else:
        carry0 = (zeros, zeros, zeros, zeros - 30.0)
    carry, ys = lax.scan(step, carry0, gates_x.transpose(1, 0, 2))
    y = ys.transpose(1, 0, 2, 3).reshape(b, t, di_l).astype(x.dtype)
    out = y @ gather_fsdp(ctx, p["w_out"], 1)
    new_state = None
    if state is not None:
        new_state = {"h": carry[0], "c": carry[1], "n": carry[2], "m": carry[3]}
    return sp_out(ctx, out), new_state
