"""Public wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernels run compiled
(interpret=False); on CPU the *model code* uses the pure jnp paths so
dry-runs lower to ordinary HLO, while tests run the Pallas kernel bodies
in interpret mode against the references.
"""
from __future__ import annotations

import collections
from functools import partial

import jax

from . import ref
from . import pam4 as pam4_k
from . import onn_layer as onn_k
from . import attention as attn_k
from ..models.layers import blocked_attention


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------- pam4 -------------------------------

@partial(jax.jit, static_argnames=("bits",))
def pam4_quantize_encode(g, scale, bits: int = 8):
    if _on_tpu():
        # interpret=None auto-resolves to compiled on TPU
        return pam4_k.pam4_quantize_encode(g, scale, bits)
    return ref.pam4_quantize_encode_ref(g, scale, bits, g.shape[-1])


@partial(jax.jit, static_argnames=("bits", "n"))
def pam4_decode_dequantize(total, scale, bits: int, n: int):
    if _on_tpu():
        return pam4_k.pam4_decode_dequantize(total, scale, bits, n)
    u_avg = ref.pam4_qmean_ref(total, n)
    return ref.pam4_decode_dequantize_ref(u_avg, scale, bits)


# ----------------------------- onn layer ----------------------------

@partial(jax.jit, static_argnames=("relu",))
def onn_layer(x, u, d, b, relu: bool = True):
    if _on_tpu():
        return onn_k.onn_layer(x, u, d, b, relu=relu)
    return ref.onn_layer_ref(x, u, d, b, relu=relu)


# ---------------------------- attention -----------------------------

# trace-time count of the model's attention dispatch, keyed by path
ATTENTION_PATHS: collections.Counter = collections.Counter()


def flash_attention(q, k, v, causal: bool = True):
    """Multi-head GQA attention for training and prefill. q: (b, hq, sq, d),
    k/v: (b, hkv, skv, d[v]).  The fused kernel (``kernels.attention``)
    where it runs and takes the shapes; ``blocked_attention`` elsewhere
    (CPU, MLA's V head dim, lengths off the kernel's tiles)."""
    if attn_k.use_kernel() and attn_k.supports(q.shape, k.shape, v.shape):
        path, fn = "attn_fused", attn_k.flash_attention
    else:
        path, fn = "attn_blocked", blocked_attention
    ATTENTION_PATHS[path] += 1
    with jax.named_scope(path):
        return fn(q, k, v, causal=causal)
