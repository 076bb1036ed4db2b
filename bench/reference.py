"""Plain reference of what the cells run, in the parts that do not depend
on the model: matrix-product arithmetic, synthetic tokens, the norms,
rotary and attention that the families' layers share, AdamW, the
data-parallel exchange, and the served-token gap.  Each model family's
layers, weights and loss are in ``bench/families/<family>.py``.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no batching tricks, no sharding.  It imports nothing
of the program under test; weights and tokens are remade from the seed
with the same recipes the program documents (a family's
``init_weights``, ``synthetic_tokens``), so the comparison never reads a
value the program computed.

``Arith`` selects how matrix products round their operands: ``f32`` is
the reference; ``fp8`` rounds both operands of every product to
float8_e4m3 (per-tensor amax scale) with a straight-through gradient --
the control, one precision step below the bf16 the configurations state.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0            # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Arith:
    mode: str = "f32"            # 'f32' | 'fp8'

    def r(self, x):
        """An operand of a matrix product, as this arithmetic sees it."""
        x = x.astype(jnp.float32)
        if self.mode == "f32":
            return x
        s = jnp.maximum(jnp.max(jnp.abs(lax.stop_gradient(x))), 1e-30) / F8_MAX
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return x + lax.stop_gradient(q - x)

    def mm(self, a, b):
        return jnp.matmul(self.r(a), self.r(b), precision=HI)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.r(a), self.r(b), precision=HI)


F32 = Arith("f32")
FP8 = Arith("fp8")


# ------------------------------------------------------------- tokens
def synthetic_tokens(vocab: int, seq_len: int, batch: int, seed: int,
                     step: int, zipf_a: float = 1.2) -> np.ndarray:
    """(batch, seq_len + 1) int32: a Zipfian unigram draw where half the
    positions instead follow a fixed bigram shift of the previous token.
    Deterministic in (seed, step)."""
    rng0 = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -zipf_a
    probs /= probs.sum()
    shift = rng0.integers(1, vocab, size=vocab)
    rng = np.random.default_rng((seed * 1_000_003 + step) * 65_537)
    t = seq_len + 1
    base = rng.choice(vocab, size=(batch, t), p=probs)
    follow = rng.random((batch, t)) < 0.5
    out = base.copy()
    for i in range(1, t):
        out[:, i] = np.where(follow[:, i], shift[out[:, i - 1]], base[:, i])
    return out.astype(np.int32)


# ------------------------------------------------------------ forward
def rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def rope(x, theta: float):
    """x: (b, t, heads, hd); rotate-half over the whole head."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs   # (t, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, ar: Arith, chunk: int = 512):
    """Causal GQA.  q: (b, t, H, hd), k/v: (b, t, KV, hd).  Queries go in
    chunks so the (chunk, t) score block, not (t, t), is live."""
    b, t, H, hd = q.shape
    kv = k.shape[2]
    rep = H // kv
    c = min(chunk, t)
    assert t % c == 0, (t, c)
    qs = (q * hd ** -0.5).reshape(b, t // c, c, kv, rep, hd)
    qs = jnp.moveaxis(qs, 1, 0)                     # (n, b, c, kv, rep, hd)
    cols = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qc, i = args
        s = ar.einsum("bqgrd,bkgd->bgrqk", qc, k)
        rows = i * c + jnp.arange(c)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ar.einsum("bgrqk,bkgd->bqgrd", p, v)

    out = lax.map(one, (qs, jnp.arange(t // c)))    # (n, b, c, kv, rep, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, H * hd)


# ------------------------------------------------------------ training
@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0

    @classmethod
    def from_config(cls, c: dict) -> "AdamW":
        return cls(**{f.name: float(c[f.name])
                      for f in dataclasses.fields(cls)})


@partial(jax.jit, static_argnums=(0, 1, 2))
def _loss_and_grad(loss, dm, ar, w, tokens):
    return jax.value_and_grad(lambda p: loss(dm, ar, p, tokens))(w)


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norm in f32, flattened order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _adam_apply(opt: AdamW, w, grads: tuple, factors: tuple):
    """Params after step t = len(grads): the f32 moments are rebuilt from
    the clipped gradients of steps 1..t (m_t, v_t are functions of those
    alone), so no moment tree has to stay resident between steps.
    Decoupled weight decay applies to every leaf of rank >= 2 in the
    stacked layout."""
    t = float(len(grads))

    def upd(p, *gs):
        m = v = jnp.zeros(p.shape, jnp.float32)
        for g, c in zip(gs, factors):
            g32 = g.astype(jnp.float32) * c
            m = opt.b1 * m + (1 - opt.b1) * g32
            v = opt.b2 * v + (1 - opt.b2) * g32 * g32
        mhat = m / (1 - opt.b1 ** t)
        vhat = v / (1 - opt.b2 ** t)
        step = mhat / (jnp.sqrt(vhat) + opt.eps)
        p32 = p.astype(jnp.float32)
        if p.ndim >= 2:
            step = step + opt.weight_decay * p32
        return (p32 - opt.lr * step).astype(p.dtype)

    return jax.tree.map(upd, w, *grads)


@dataclasses.dataclass(frozen=True)
class Exchange:
    """How the data-parallel chips' gradients become one.

    * ``mean``: the exact mean, as an all-reduce computes it;
    * ``optinc``: OptINC's in-network average of B-bit codes (the paper's
      eq. 3).  Each chip's gradient, as one f32 stream over the leaves in
      flattened order, is cut into blocks of ``block`` elements (the last
      padded with zeros).  A block's scale s is its largest |g| over every
      chip.  Each chip codes it as q = clip(round(g / s * L), -L, L), with
      L = 2^(B-1) - 1; the network returns round(sum(q + L) / n) - L
      (half to even), which decodes as that code times s / L;
    * ``none``: each chip keeps its own gradient (chip 0's is followed):
      the exchange left out, a fault.
    """
    mode: str = "mean"
    bits: int = 8
    block: int = 2048


MEAN = Exchange("mean")
TINY_F32 = float(np.finfo(np.float32).tiny)


@partial(jax.jit, static_argnums=(0,))
def _optinc_chunk(ex: Exchange, g):
    """g: (chips, blocks, block) f32 -> the decoded average (blocks, block)."""
    n = g.shape[0]
    lv = 2 ** (ex.bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(g), axis=(0, 2)), TINY_F32)
    zero = s <= TINY_F32
    safe = jnp.where(zero, 1.0, s)
    q = jnp.clip(jnp.round(g / safe[None, :, None] * lv), -lv, lv)
    q = jnp.where(zero[None, :, None], 0.0, q).astype(jnp.int32)
    avg = jnp.round(jnp.sum(q + lv, axis=0).astype(jnp.float32) / n)
    return (avg - lv) * (safe[:, None] / lv)


@jax.jit
def _flat(tree):
    return jnp.concatenate([x.astype(jnp.float32).reshape(-1)
                            for x in jax.tree.leaves(tree)])


def exchange(grads: list, ex: Exchange, dev):
    """One gradient tree, on ``dev``, from each chip's (``grads``, which
    is emptied so that no chip's tree outlives its use)."""
    if len(grads) == 1 or ex.mode == "none":
        return jax.device_put(grads[0], dev)
    if ex.mode == "mean":
        grads = [jax.device_put(g, dev) for g in grads]
        return jax.tree.map(
            lambda *gs: (sum(x.astype(jnp.float32) for x in gs)
                         / len(gs)).astype(gs[0].dtype), *grads)
    assert ex.mode == "optinc", ex
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        grads[0])
    flats = [_flat(g) for g in grads]          # each on its own chip
    grads.clear()
    n = flats[0].shape[0]
    chunk = ex.block * 16384
    out = []
    for a in range(0, n, chunk):
        parts = [jax.device_put(f[a:a + chunk], dev) for f in flats]
        m = parts[0].shape[0]
        pad = (-m) % ex.block
        g = jnp.stack([jnp.pad(x, (0, pad)) for x in parts])
        out.append(_optinc_chunk(ex, g.reshape(len(parts), -1, ex.block)
                                 ).reshape(-1)[:m])
    del flats
    flat = jnp.concatenate(out)
    del out
    leaves, treedef = jax.tree.flatten(like)
    new, o = [], 0
    for x in leaves:
        new.append(flat[o:o + x.size].reshape(x.shape).astype(x.dtype))
        o += x.size
    return jax.tree.unflatten(treedef, new)


def train(loss, dm, opt: AdamW, w, batches, ar: Arith = F32, chips: int = 1,
          ex: Exchange = MEAN) -> dict:
    """Runs len(batches) AdamW steps of a family's ``loss(dm, ar, w,
    tokens)`` from weights ``w`` (consumed), with each step's rows split
    evenly over ``chips`` data-parallel chips, each chip's mean-loss
    gradient taken alone and the chips' gradients
    made one by ``ex``.  The chips' gradients are computed on as many
    devices as there are, round robin.  Returns the loss of each step
    (the mean over chips), the per-leaf norms of the first clipped
    gradient, and the final weights."""
    devs = jax.devices()
    dev = devs[0]
    grads, factors, losses, g1 = [], [], [], None
    for tokens in batches:
        rows = np.array_split(np.asarray(tokens), chips)
        per_chip, lvals = [], []
        for c, r in enumerate(rows):
            d = devs[c % len(devs)]
            wc = w if d == dev else jax.device_put(w, d)
            lval, g = _loss_and_grad(loss, dm, ar, wc, jax.device_put(r, d))
            per_chip.append(g)
            lvals.append(lval)
            del wc
        g = exchange(per_chip, ex, dev)
        del per_chip
        norms = leaf_norms(g)
        gnorm = jnp.sqrt(jnp.sum(jnp.square(norms)))
        c = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-12))
        if g1 is None:
            g1 = np.asarray(norms * c)
        grads.append(g)
        factors.append(c)
        losses.append(float(np.mean([float(x) for x in lvals])))
        w = _adam_apply(opt, w, tuple(grads), tuple(factors))
    return {"losses": losses, "g1_norms": g1, "weights": w}


# ------------------------------------------------------------- serving
def served_gap(ref_logits, tokens) -> float:
    """Widest gap by which a served token's reference logit lies below
    the reference's best at that position."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens)
    return float(np.max(ref.max(-1) - ref[np.arange(len(tok)), tok]))
