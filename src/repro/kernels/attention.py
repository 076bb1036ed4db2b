"""Fused flash attention for training and prefill (Pallas TPU, forward and
backward).

A wrapper over the splash attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): one forward
kernel (online softmax over KV tiles, the score tile never leaves VMEM)
and two backward kernels (dq; dk and dv), joined by a custom VJP.  GQA
is the multi-head kernel over ``hkv`` KV heads, each serving ``h / hkv``
query heads, vmapped over the batch: K and V are never repeated.

Causal masking is splash's ``CausalMask`` with the ``skv - sq`` offset
(row r sees columns ``<= r + skv - sq``, as ``blocked_attention``); the
mask's block info skips every tile wholly above the diagonal in the
forward and in both backward kernels.

Precision: the model's operands go in as they are (bf16 in every
configuration that trains), with the ``1/sqrt(hd)`` scale applied to q
and rounded back to q's dtype, which is what XLA's DEFAULT precision
does to ``blocked_attention``'s f32 operands on TPU.  Products
accumulate in f32; the running max, normaliser and logsumexp, and the
dk / dv / dq accumulators are f32; the output and the gradients go back
to the operands' dtype.

``supports`` says which shapes the kernel takes; ``use_kernel`` where
it runs (compiled on TPU).  ``kernels.ops.flash_attention`` dispatches
between this and ``models.layers.blocked_attention`` from both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from ..photonics.config import resolve_interpret

BLOCKS = (512, 256, 128)   # tile edges tried, largest first

# test hook: True forces the (interpreted, off-TPU) kernel into the
# model's dispatch, False forces blocked_attention, None = platform
FORCE_KERNEL: bool | None = None


def use_kernel() -> bool:
    """Should the model's attention run this kernel?  Compiled on TPU;
    elsewhere ``blocked_attention`` (interpret mode is a test vehicle).
    The module-level ``FORCE_KERNEL`` test hook wins."""
    if FORCE_KERNEL is not None:
        return bool(FORCE_KERNEL)
    return jax.default_backend() == "tpu"


def block(n: int) -> int | None:
    """The tile edge for a sequence of ``n``: the largest of ``BLOCKS``
    that divides it, or None when none does."""
    return next((b for b in BLOCKS if n % b == 0), None)


def supports(q_shape, k_shape, v_shape) -> bool:
    """Can the kernel take q (b, h, sq, hd), k (b, hkv, skv, hd),
    v (b, hkv, skv, hdv)?  Equal q/k and v head dims, a multiple of the
    128 lanes, and both lengths a multiple of a tile."""
    hd, hdv = q_shape[-1], v_shape[-1]
    return (hd == hdv and hd % 128 == 0 and block(q_shape[2]) is not None
            and block(k_shape[2]) is not None)


def _kernel(h: int, sq: int, skv: int, causal: bool, interpret: bool):
    bq, bkv = block(sq), block(skv)
    one = (splash.CausalMask((sq, skv), offset=skv - sq) if causal
           else splash.FullMask((sq, skv)))
    sizes = splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv)
    return splash.make_splash_mha(
        splash.MultiHeadMask([one] * h), block_sizes=sizes, head_shards=1,
        q_seq_shards=1, interpret=interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True,
                    interpret: bool | None = None) -> jnp.ndarray:
    """GQA attention, differentiable.  q: (b, h, sq, hd), k/v:
    (b, hkv, skv, hd) of q's dtype, with ``supports`` true and, where
    causal, sq <= skv.  Returns (b, h, sq, hd) in q's dtype."""
    b, h, sq, hd = q.shape
    skv = k.shape[2]
    kernel = _kernel(h, sq, skv, causal, resolve_interpret(interpret))
    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    return jax.vmap(kernel)(qs, k, v)
