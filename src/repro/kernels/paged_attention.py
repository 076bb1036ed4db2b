"""Pallas TPU kernel: paged-attention decode straight over the KV pool.

The serving tier's gather path (``models.layers.paged_gather`` feeding
``decode_attention``) materializes every active sequence's pages as a
contiguous ``(b, hkv, nb * page, hd)`` buffer before the softmax — an
O(active * max_seq) HBM copy per decode step per layer, twice (K and V).
This kernel attends over the physical pool IN PLACE instead: the grid
runs ``(slots, kv_heads, page_tiles)`` with the page-tile axis fastest,
each slot's page-table row is scalar-prefetched (SMEM) so the K and V
``BlockSpec`` index maps can steer the next page's DMA straight out of
the pool into VMEM, and a running online-softmax state ``(m, l, acc)``
in VMEM scratch folds one ``(page, hd)`` tile into the slot's attention
output per grid step — no contiguous KV copy ever exists.

Semantics match ``decode_attention`` over the gathered view exactly:
positions ``>= lengths[slot]`` are masked to ``NEG_INF`` score (zero
weight), which covers both the zero tail of a sequence's last page and
every page-table entry still pointing at the reserved null page 0 —
whatever those pages hold is masked out by the position test, never by
trusting pool contents.  Query scaling, f32 accumulation (KV pages may
be stored bf16 — ``ServeConfig.kv_dtype``), the GQA query-group
broadcast and the ``max(l, 1e-30)`` guard are the same ops in the same
precision; the only difference from the gather path is the online
tile-by-tile association of the softmax sums, so kernel and oracle agree
to float-associativity (~1e-6), not bitwise.

VMEM budget per grid step (f32): a ``(rep, hd)`` query block, two
``(page, hd)`` KV pages and the ``(rep, hd + 2)`` scratch state — for
the largest serving shapes in the repo (rep 8, hd 128, page 64) well
under 100 KiB against the ~16 MiB/core budget; pallas double-buffers the
next page's fetch behind the current tile's FLOPs.

``use_kernel`` decides dispatch: the kernel runs compiled on TPU;
everywhere else ``decode_backend='paged'`` falls back to the XLA gather
path (``models.blocks.gqa_decode_paged``), which stays bit-exact with
``decode_backend='gather'`` by construction.  Tests force the
interpreted kernel (``interpret=True`` here, ``FORCE_KERNEL`` for the
engine path) to run the same numerics on CPU CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..photonics.config import resolve_interpret

NEG_INF = -1e30  # models.layers.NEG_INF: finite, exp(NEG_INF - m) == 0.0

# test hook: True forces the (interpreted, off-TPU) kernel into the
# serving dispatch, False forces the gather fallback, None = platform
FORCE_KERNEL: bool | None = None


def use_kernel(flag: bool | None = None) -> bool:
    """Should ``decode_backend='paged'`` run the Pallas kernel?  Compiled
    on TPU; elsewhere the XLA gather path is the fallback (interpret-mode
    pallas is a test vehicle, not a serving path).  Explicit flag (or the
    module-level ``FORCE_KERNEL`` test hook) wins."""
    if flag is not None:
        return bool(flag)
    if FORCE_KERNEL is not None:
        return bool(FORCE_KERNEL)
    return jax.default_backend() == "tpu"


def _paged_attention_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                            m_ref, l_ref, acc_ref, *, page_size: int,
                            n_blocks: int):
    """One (slot, kv_head, page_tile) grid step: fold one physical page
    into the slot's online-softmax state; write the output at the last
    tile.  pt_ref/len_ref are the scalar-prefetched page tables (flat)
    and per-slot valid counts — already consumed by the K/V index maps,
    len_ref again here for the validity mask."""
    i, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                    # (rep, hd) f32 scaled
    k = k_ref[0, 0].astype(jnp.float32)                # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    # HIGHEST: at default precision the MXU rounds f32 operands to bf16,
    # which put the compiled kernel ~1e-3 off the f32 gather oracle
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)  # (rep, page)
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    s = jnp.where(pos < len_ref[i], s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    m_ref[...] = m_cur
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    page_table: jnp.ndarray, lengths: jnp.ndarray, *,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Decode attention for a packed slot batch, read straight off the
    physical page pool.

    q: (b, h, 1, hd) one pending query per slot; k_pool/v_pool:
    (P, hkv_local, page, hd) shared physical pages (any float dtype —
    accumulation is f32); page_table: (b, nb) per-slot page ids in
    logical-block order (null page 0 beyond a slot's allocation);
    lengths: (b,) valid cache positions per slot — the ``lengths + 1``
    the gather path passes to ``decode_attention`` (the pending token's
    KV must already be written to its page).  Returns (b, h, 1, hd) in
    q.dtype, equal to ``decode_attention(ctx, q, paged_gather(k_pool,
    page_table), paged_gather(v_pool, page_table), lengths)`` up to
    online-softmax float associativity.
    """
    interpret = resolve_interpret(interpret)
    b, h, one, hd = q.shape
    assert one == 1, q.shape
    n_pages, hkv, ps, _ = k_pool.shape
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    nb = page_table.shape[1]
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, hkv, rep, hd)
    pt = page_table.reshape(b * nb).astype(jnp.int32)

    def q_map(i, g, j, pt_ref, len_ref):
        return (i, g, 0, 0)

    def kv_map(i, g, j, pt_ref, len_ref):
        # the scalar-prefetched page table steers the DMA: page tile j of
        # slot i is fetched from wherever that slot's j-th page lives
        return (pt_ref[i * nb + j], g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), q_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), q_map),
        scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attention_kernel, page_size=ps,
                          n_blocks=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, hd), jnp.float32),
        interpret=interpret,
    )(pt, lengths.astype(jnp.int32), qf, k_pool, v_pool)
    return out.reshape(b, h, 1, hd).astype(q.dtype)
