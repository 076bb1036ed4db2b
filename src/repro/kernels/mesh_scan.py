"""Pallas TPU kernel: the fused L-layer MZI-mesh cascade in VMEM.

``photonics.mesh.MZIMesh.apply`` lowers to one XLA gather + FMA per
rotation layer under ``lax.scan`` — L round-trips of the batch tile
through HBM for an L-layer Clements cascade.  This kernel keeps the
whole compiled program resident instead: the three (L, m) layer stacks
(partner permutation ``perm``, diagonal ``ca``, off-diagonal ``sa``)
plus one batch tile live in VMEM together, and a ``fori_loop`` applies
all L layers back to back — ONE HBM read and ONE HBM write per batch
tile for the entire mesh, however deep it is.

``mesh_scan_blocks`` is the block-batched form: the stacked block axis
of ``ApproxLayerProgram`` (B same-width meshes applied to the same — or
a per-block — batch) is folded into the ``pallas_call`` grid as
``grid = (B, batch_tiles)`` instead of an outer ``jax.vmap`` of B
separate kernel launches.  The batch-tile axis iterates fastest, so
each block's (L, m) stacks are fetched into VMEM once and reused across
every batch tile (pallas double-buffers the per-block fetch while the
previous block computes); a shared batch tile is re-read per block from
its HBM-resident pad, never re-materialized per block in XLA.

The per-layer wire shuffle ``y[..., perm]`` is not a native TPU lane
operation; it is realized as a one-hot matmul on the MXU:

    P[i, j] = (perm[j] == i)          (built in-VMEM from an iota)
    y[..., perm] = y @ P

so a layer is one (blk_b, m) x (m, m) MXU pass + a fused VPU FMA.  When
the full (L, m, m) one-hot stack fits a VMEM scratch budget
(``ONEHOT_CACHE_BYTES``), it is built ONCE per block — at the first
batch tile, persisting in scratch across grid steps — instead of
rebuilt from the iota compare inside every tile's layer loop.  The sign
column and an optional diagonal epilogue (the Sigma_a ``d`` scale of
``ApproxLayerProgram``) ride along as free pre/post VPU multiplies, so
the whole ``diag(post) . G_1^T..G_K^T . diag(pre)`` chain is one kernel.

PhaseNoise theta drift is drawn IN-KERNEL: with ``theta_std > 0`` each
layer of a block's grid step derives its row of an (L, m) standard-normal
field from a per-block uint32 seed (folded off the step key by the
caller) via a counter-based splitmix32 hash + Box-Muller — no perturbed
(ca, sa) stacks are ever materialized in XLA, and the same portable
uint32 arithmetic runs compiled and interpreted.  ``theta_std == 0``
traces NONE of the noise code (no seed operand, no extra ops), so the
zero-noise kernel stays bit-exact with the noise-free parity rows.  Shot noise (additive, on
the output) stays an XLA epilogue in ``photonics.mesh``.

VMEM budget (f32, the compiled-TPU case): the layer stacks cost
3 * L * m_pad * 4 bytes and the tile 2 * blk_b * m_pad * 4; the one-hot
scratch cache adds L * m_pad^2 * 4 when enabled (capped at
``ONEHOT_CACHE_BYTES`` = 4 MiB, falling back to the in-loop iota build
for deeper/wider programs); for the deepest program in the repo
(m = 256, L ~ 2m = 512) that is ~1.6 MiB + ~0.5 MiB — comfortably
inside the ~16 MiB/core budget with the default blk_b = 128.

``interpret`` auto-detects via ``photonics.resolve_interpret`` (compiled
on TPU, interpreted everywhere else); the interpreted path runs the
identical one-hot math, so CPU CI exercises the same numerics the TPU
executes.  ``photonics.mesh`` keeps the pure-XLA scan as the fallback
backend (``mesh_backend='xla'``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..photonics.config import resolve_interpret

DEFAULT_BLK_B = 128        # batch rows per tile (PhotonicsConfig.blk_b = 0)
ONEHOT_CACHE_BYTES = 4 * 2 ** 20  # VMEM budget for the per-block one-hot stack


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


# ------------------------------ in-kernel PRNG ------------------------------

def _mix32(x):
    """splitmix32-style avalanche of a uint32 counter word."""
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def _uniform24(h, dt):
    """The top 24 bits of a uint32 word as a float in [0, 2^24).  Goes
    through int32 (exact: the value is < 2^24) because Mosaic has no
    uint32 -> float cast."""
    return (h >> jnp.uint32(8)).astype(jnp.int32).astype(dt)


def _normal_row(seed, row, m: int, dt):
    """(1, m) standard normals of layer ``row`` from one uint32 seed,
    counter-based.

    Two independent uint32 hash streams per (layer, wire) counter feed a
    Box-Muller transform.  Plain jnp uint32 arithmetic — identical bits
    compiled and interpreted, unlike ``pltpu.prng_random_bits`` (which
    has no lowering in the Pallas interpreter), so CPU CI can
    statistically validate the same draws the TPU makes.  The kernel
    draws each layer's row inside its layer loop: Mosaic cannot slice a
    precomputed (L, m) field at a traced layer index.
    """
    col = jax.lax.broadcasted_iota(jnp.uint32, (1, m), 1)
    base = ((row.astype(jnp.uint32) * jnp.uint32(m) + col)
            * jnp.uint32(0x9E3779B9) + seed)
    h1 = _mix32(base)
    h2 = _mix32(base ^ jnp.uint32(0x85EBCA6B))
    # 24-bit mantissa uniforms; u1 in (0, 1] keeps the log finite
    u1 = (_uniform24(h1, dt) + 1.0) * jnp.asarray(2.0 ** -24, dt)
    u2 = _uniform24(h2, dt) * jnp.asarray(2.0 ** -24, dt)
    r = jnp.sqrt(jnp.asarray(-2.0, dt) * jnp.log(u1))
    return r * jnp.cos(jnp.asarray(2.0 * jnp.pi, dt) * u2)


# --------------------------------- kernel -----------------------------------

def _mesh_scan_blocks_kernel(*refs, n_layers: int, transpose: bool,
                             x_blocked: bool, theta_std: float,
                             cache_onehot: bool):
    """One (block, batch-tile) grid step of the fused cascade.

    refs: perm, ca, sa, pre, post, x[, seed] | out | [onehot scratch].
    """
    if theta_std > 0.0:
        (perm_ref, ca_ref, sa_ref, pre_ref, post_ref, x_ref, seed_ref,
         y_ref, *scratch) = refs
    else:
        (perm_ref, ca_ref, sa_ref, pre_ref, post_ref, x_ref,
         y_ref, *scratch) = refs
        seed_ref = None
    oh_ref = scratch[0] if cache_onehot else None

    dt = y_ref.dtype
    m = pre_ref.shape[-1]
    y = (x_ref[0] if x_blocked else x_ref[...]) * pre_ref[0]
    # wire[i, j] = i; comparing against a perm row makes the one-hot
    # permutation matrix P with P[i, j] = (perm[j] == i), so y @ P is
    # y[..., perm] (TPU needs >= 2-D iota)
    wire = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)

    if cache_onehot:
        # built once per block: the batch-tile axis is the fastest grid
        # dim and scratch persists across grid steps, so tiles j > 0
        # reuse the stack tile j == 0 materialized
        @pl.when(pl.program_id(1) == 0)
        def _build():
            def build(l, carry):
                p = perm_ref[0, pl.ds(l, 1), :]               # (1, m)
                oh_ref[pl.ds(l, 1)] = ((wire == p).astype(dt))[None]
                return carry
            jax.lax.fori_loop(0, n_layers, build, 0)

    if theta_std > 0.0:
        # one drift field per BLOCK and apply — identical across the
        # block's batch tiles (one physical mesh per block), varying only
        # with the per-block seed the caller folded off the step key
        seed = seed_ref[0].astype(jnp.uint32)                 # (1, 1)

    def body(i, y):
        l = (n_layers - 1 - i) if transpose else i
        p = perm_ref[0, pl.ds(l, 1), :]                       # (1, m)
        ca = ca_ref[0, pl.ds(l, 1), :]
        sa = sa_ref[0, pl.ds(l, 1), :]
        if cache_onehot:
            onehot = oh_ref[pl.ds(l, 1)][0]                   # (m, m)
        else:
            # HIGHEST precision: the MXU's default truncates f32 inputs
            # to bf16, which would round y on every one of the L layers —
            # selection through an exact 0/1 matrix must stay exact
            onehot = (wire == p).astype(dt)
        if theta_std > 0.0:
            # pipeline.PhaseNoise.perturb, per layer: one gaussian per
            # wire, symmetrized over the partner permutation (the
            # one-hot matmul IS g[perm]), antisymmetric sign ->
            # coherent theta -> theta + eps on both wires of each MZI;
            # untouched wires (perm == self) get sign 0, eps 0 exactly
            g_row = _normal_row(seed, l, m, dt)
            g_p = jnp.dot(g_row, onehot, preferred_element_type=dt,
                          precision=jax.lax.Precision.HIGHEST)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
            sgn = jnp.sign(lane - p).astype(dt)
            delta = jnp.asarray(0.5 ** 0.5, dt) * (g_row + g_p)
            eps = jnp.asarray(theta_std, dt) * delta * sgn
            ce, se = jnp.cos(eps), jnp.sin(eps)
            ca, sa = ca * ce - sa * se, sa * ce + ca * se
        y_p = jnp.dot(y, onehot, preferred_element_type=dt,
                      precision=jax.lax.Precision.HIGHEST)
        # forward applies G^T (the compiled sa), transpose applies G
        return ca * y - sa * y_p if transpose else ca * y + sa * y_p

    y = jax.lax.fori_loop(0, n_layers, body, y)
    y_ref[...] = (y * post_ref[0]).astype(dt)[None]


# ------------------------------- dispatchers --------------------------------

def mesh_scan_blocks(signs: jnp.ndarray, perm: jnp.ndarray, ca: jnp.ndarray,
                     sa: jnp.ndarray, x: jnp.ndarray, *,
                     x_block_axis: bool = False, transpose: bool = False,
                     post_scale: jnp.ndarray | None = None,
                     interpret: bool | None = None, blk_b: int = 0,
                     theta_std: float = 0.0,
                     seeds: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply B stacked rotation-layer programs in ONE kernel launch.

    ``signs`` is (B, m); ``perm``/``ca``/``sa`` are the (B, L, m) stacks
    of ``photonics.mesh._stack_meshes``.  ``x`` is shared across blocks
    (``(..., m)``) or carries its own block axis at -2
    (``x_block_axis``, ``(..., B, m)``); the result is ``(..., B, m)`` —
    the contract of ``photonics.mesh._apply_stacked``, without its outer
    ``jax.vmap`` of B separate ``pallas_call``s: the block axis is a
    grid dimension, batch tiles iterate fastest, and each block's stacks
    are fetched into VMEM once.

    ``post_scale`` (B, m) is each block's fused diagonal epilogue.
    ``theta_std`` > 0 enables the in-kernel PhaseNoise theta drift,
    seeded per block from ``seeds`` (B,) uint32; 0 compiles the exact
    noise-free kernel (statically — no seed operand exists).
    ``blk_b`` tiles the batch (0 = ``DEFAULT_BLK_B``).
    """
    interpret = resolve_interpret(interpret)
    n_blocks, n_layers, m = perm.shape
    dt = jnp.result_type(x.dtype, ca.dtype)
    if theta_std > 0.0 and seeds is None:
        raise ValueError("mesh_scan_blocks: theta_std > 0 needs per-block "
                         "uint32 seeds")

    batch_shape = x.shape[:-2] if x_block_axis else x.shape[:-1]
    if x_block_axis:
        if x.shape[-2] != n_blocks:
            raise ValueError(f"x block axis {x.shape[-2]} != {n_blocks}")
        # (..., B, m) -> (B, batch, m): each block's batch pad is a
        # contiguous HBM operand the grid tiles at (i, j)
        y = jnp.moveaxis(x.astype(dt).reshape(-1, n_blocks, m), 1, 0)
    else:
        y = x.astype(dt).reshape(-1, m)
    batch = y.shape[-2]
    if batch == 0:
        return jnp.zeros(batch_shape + (n_blocks, m), dt)

    ones = jnp.ones((n_blocks, m), dt)
    pre = ones if transpose else signs.astype(dt)
    post = signs.astype(dt) if transpose else ones
    if post_scale is not None:
        post = post * post_scale.astype(dt)

    # pad wires to the 128-lane tile (identity rotations: perm = self,
    # ca = 1, sa = 0, so padded lanes stay at their zero-padded inputs)
    # and the batch to the chosen sublane tile
    m_pad = _round_up(max(m, 1), 128)
    blk_b = int(blk_b) or DEFAULT_BLK_B
    blk_b = min(blk_b, _round_up(batch, 8))
    b_pad = _round_up(batch, blk_b)
    if m_pad != m:
        pad_ids = jnp.broadcast_to(jnp.arange(m, m_pad, dtype=perm.dtype),
                                   (n_blocks, n_layers, m_pad - m))
        perm = jnp.concatenate([perm, pad_ids], axis=-1)
        ca = jnp.pad(ca, ((0, 0), (0, 0), (0, m_pad - m)), constant_values=1)
        sa = jnp.pad(sa, ((0, 0), (0, 0), (0, m_pad - m)))
        pre = jnp.pad(pre, ((0, 0), (0, m_pad - m)), constant_values=1)
        post = jnp.pad(post, ((0, 0), (0, m_pad - m)), constant_values=1)
    bp = b_pad - batch
    if x_block_axis:
        y = jnp.pad(y, ((0, 0), (0, bp), (0, m_pad - m)))
    else:
        y = jnp.pad(y, ((0, bp), (0, m_pad - m)))

    n_tiles = b_pad // blk_b
    # the one-hot scratch cache only pays when >1 tile reuses it and the
    # whole (L, m_pad, m_pad) stack fits the VMEM budget
    oh_bytes = n_layers * m_pad * m_pad * jnp.dtype(dt).itemsize
    cache_onehot = n_tiles > 1 and oh_bytes <= ONEHOT_CACHE_BYTES

    # per-block rows ride as (B, 1, m_pad) so every block's last two dims
    # equal the array's (Mosaic's (8, 128) tiling rule)
    stack_spec = pl.BlockSpec((1, n_layers, m_pad), lambda i, j: (i, 0, 0))
    col_spec = pl.BlockSpec((1, 1, m_pad), lambda i, j: (i, 0, 0))
    in_specs = [stack_spec, stack_spec, stack_spec, col_spec, col_spec]
    operands = [perm, ca.astype(dt), sa.astype(dt), pre[:, None],
                post[:, None]]
    if x_block_axis:
        in_specs.append(pl.BlockSpec((1, blk_b, m_pad),
                                     lambda i, j: (i, j, 0)))
    else:
        in_specs.append(pl.BlockSpec((blk_b, m_pad), lambda i, j: (j, 0)))
    operands.append(y)
    if theta_std > 0.0:
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)))
        operands.append(seeds.astype(jnp.uint32).astype(jnp.int32)
                        .reshape(n_blocks, 1, 1))

    out = pl.pallas_call(
        functools.partial(_mesh_scan_blocks_kernel, n_layers=n_layers,
                          transpose=transpose, x_blocked=x_block_axis,
                          theta_std=float(theta_std),
                          cache_onehot=cache_onehot),
        grid=(n_blocks, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, blk_b, m_pad), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, b_pad, m_pad), dt),
        scratch_shapes=([pltpu.VMEM((n_layers, m_pad, m_pad), dt)]
                        if cache_onehot else []),
        interpret=interpret,
    )(*operands)
    # (B, batch, m) -> (..., B, m)
    out = jnp.moveaxis(out[:, :batch, :m], 0, 1)
    return out.reshape(batch_shape + (n_blocks, m))


def mesh_scan(signs: jnp.ndarray, perm: jnp.ndarray, ca: jnp.ndarray,
              sa: jnp.ndarray, x: jnp.ndarray, transpose: bool = False,
              post_scale: jnp.ndarray | None = None,
              interpret: bool | None = None, blk_b: int = 0,
              theta_std: float = 0.0,
              seed: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply a compiled rotation-layer stack to ``x`` in one fused kernel.

    Semantically identical to ``MZIMesh.apply`` (o @ x over the last axis,
    o^T @ x when ``transpose``), with an optional fused diagonal epilogue
    ``post_scale`` multiplied into the output.  ``perm``/``ca``/``sa`` are
    the (L, m) stacks of ``MZIMesh``; ``signs`` is its (m,) sign column.
    Arbitrary leading batch dims on ``x`` are flattened into the grid.
    The single-mesh entry point is the B = 1 case of
    ``mesh_scan_blocks``; ``theta_std``/``seed`` enable the in-kernel
    PhaseNoise theta drift.
    """
    out = mesh_scan_blocks(
        signs[None], perm[None], ca[None], sa[None], x,
        x_block_axis=False, transpose=transpose,
        post_scale=None if post_scale is None else post_scale[None],
        interpret=interpret, blk_b=blk_b, theta_std=theta_std,
        seeds=None if seed is None else jnp.reshape(seed, (1,)))
    return out[..., 0, :]
