"""The dense GQA decoder: plain reference, program configuration and FLOP
counts.

Layer equations (pre-norm decoder, as in the configs that name this
family):

    h  = rmsnorm(x) * g_attn
    q, k, v = h Wq, h Wk, h Wv ; rotary on q, k (rotate-half, full head)
    a  = softmax(q k^T / sqrt(hd) + causal) v   (GQA: head i reads kv i//rep)
    x  = x + a Wo
    h  = rmsnorm(x) * g_mlp
    x  = x + (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) * g_final  Whead

FLOPs the algorithm requires, from shapes alone:

Training (per token, forward and backward): 6 FLOP per matrix parameter
-- the attention projections, the three SwiGLU matrices and the output
head; the embedding is a gather and counts nothing -- plus causal
attention: query i reads i + 1 keys, 2 FLOP per (query, key, dim) for
q k^T and as many for p v, so 2 * H * hd * (T + 1) per token forward and
three times that with the backward pass.  Recomputation counts nothing.

Serving (forward only): a prompt of n tokens costs 2 FLOP per layer
matrix parameter per token, causal attention 2 * H * hd * n * (n + 1)
per layer, and the output head once (only the last position's logits
are taken); a decode token over ``a`` attended positions costs 2 FLOP per
layer parameter, 4 * H * hd * a per layer, and the head.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import Arith, attention, rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_layers: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   n_layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]))


def program_config(dims: Dims, model_cfg: dict):
    """The program's ``ModelConfig`` for this configuration."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=model_cfg["name"], family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff, vocab=dims.vocab,
        head_dim=dims.head_dim, rope_theta=dims.rope_theta,
        dtype=model_cfg["dtype"])


# ------------------------------------------------------------ weights
def weight_shapes(dm: Dims) -> dict:
    """The stacked-layer layout: every layer's leaf carries a leading
    layer axis.  Flattened in sorted-key order, as JAX flattens dicts."""
    d, L, hd = dm.d_model, dm.n_layers, dm.head_dim
    return {
        "embed": (dm.vocab, d),
        "final_norm": (d,),
        "layers": {
            "mlp_norm": (L, d), "norm": (L, d),
            "w_down": (L, dm.d_ff, d), "w_gate": (L, d, dm.d_ff),
            "w_up": (L, d, dm.d_ff),
            "wk": (L, d, dm.n_kv_heads * hd), "wo": (L, dm.n_heads * hd, d),
            "wq": (L, d, dm.n_heads * hd), "wv": (L, d, dm.n_kv_heads * hd),
        },
        "lm_head": (d, dm.vocab),
    }


def init_weights(dm: Dims, seed: int):
    """Seeded bf16 weights in one call on the device: one key per leaf
    (``split`` of the seed's key in flattened order), matrices
    N(0, 0.02^2), norm scales 1."""
    return _init_weights(dm, jax.random.PRNGKey(seed))


@partial(jax.jit, static_argnums=(0,))
def _init_weights(dm: Dims, key):
    shapes = weight_shapes(dm)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shp, path in zip(keys, leaves, paths):
        if len(shp) == 1 or path.endswith("norm']"):
            out.append(jnp.ones(shp, jnp.bfloat16))
        else:
            scale = 0.02 if shp[-2] > 8 else 0.5
            out.append((jax.random.normal(k, shp, jnp.float32) * scale
                        ).astype(jnp.bfloat16))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------ forward
def layer(dm: Dims, ar: Arith, x, p):
    b, t, _ = x.shape
    hd = dm.head_dim
    h = rmsnorm(x, p["norm"], dm.norm_eps)
    q = ar.mm(h, p["wq"]).reshape(b, t, dm.n_heads, hd)
    k = ar.mm(h, p["wk"]).reshape(b, t, dm.n_kv_heads, hd)
    v = ar.mm(h, p["wv"]).reshape(b, t, dm.n_kv_heads, hd)
    a = attention(rope(q, dm.rope_theta), rope(k, dm.rope_theta), v, ar)
    x = x + ar.mm(a, p["wo"])
    h = rmsnorm(x, p["mlp_norm"], dm.norm_eps)
    g = jax.nn.silu(ar.mm(h, p["w_gate"]))
    return x + ar.mm(g * ar.mm(h, p["w_up"]), p["w_down"])


def hidden(dm: Dims, ar: Arith, w, tokens, remat: bool):
    """Final-normed hidden states (b, t, d) in f32."""
    # f32 before the gather, so repeated tokens' gradients add in f32
    x = w["embed"].astype(jnp.float32)[tokens]

    def body(x, p):
        return layer(dm, ar, x, p), None
    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, w["layers"])
    return rmsnorm(x, w["final_norm"], dm.norm_eps)


# ------------------------------------------------------------ training
def loss(dm: Dims, ar: Arith, w, tokens, chunk: int = 1024):
    """Mean next-token NLL over every position of every row."""
    h = hidden(dm, ar, w, tokens[:, :-1], remat=True)
    tgt = tokens[:, 1:]
    b, t, d = h.shape
    c = min(chunk, t)
    hs = jnp.moveaxis(h.reshape(b, t // c, c, d), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(b, t // c, c), 1, 0)
    head = w["lm_head"].astype(jnp.float32)   # chunk gradients add in f32

    @jax.checkpoint
    def nll(args):
        hc, tc = args
        logits = ar.mm(hc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, tc[..., None],
                                                 -1)[..., 0])

    return jnp.sum(lax.map(nll, (hs, ts))) / (b * t)


# ------------------------------------------------------------- serving
@partial(jax.jit, static_argnums=(0, 1))
def logits_at(dm: Dims, ar: Arith, w, tokens, positions):
    """f32 logits (n, vocab) at ``positions`` of one sequence (1, t)."""
    h = hidden(dm, ar, w, tokens, remat=False)[0]
    return ar.mm(h[positions], w["lm_head"])


# --------------------------------------------------------------- FLOPs
def matmul_params(dims) -> int:
    d, hd = dims.d_model, dims.head_dim
    per_layer = (d * dims.n_heads * hd + 2 * d * dims.n_kv_heads * hd
                 + dims.n_heads * hd * d + 3 * d * dims.d_ff)
    return dims.n_layers * per_layer + d * dims.vocab


def train_flops_per_token(dims, seq_len: int) -> float:
    attn = 6 * dims.n_heads * dims.head_dim * (seq_len + 1) * dims.n_layers
    return 6.0 * matmul_params(dims) + attn


def _layer_params(dims) -> int:
    return matmul_params(dims) - dims.d_model * dims.vocab


def prefill_flops(dims, n: int) -> float:
    hd, h, L = dims.head_dim, dims.n_heads, dims.n_layers
    return (2.0 * n * _layer_params(dims) + 2.0 * h * hd * n * (n + 1) * L
            + 2.0 * dims.d_model * dims.vocab)


def decode_flops(dims, attended: int) -> float:
    hd, h, L = dims.head_dim, dims.n_heads, dims.n_layers
    return (2.0 * _layer_params(dims) + 4.0 * h * hd * attended * L
            + 2.0 * dims.d_model * dims.vocab)
