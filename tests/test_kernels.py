"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention as attn_k
from repro.kernels import onn_layer as onn_k
from repro.kernels import pam4 as pam4_k
from repro.kernels import ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("shape", [(8, 128), (32, 256), (16, 1024)])
def test_pam4_encode_kernel(bits, shape):
    g = jnp.asarray(RNG.normal(size=shape).astype(np.float32))
    scale = jnp.max(jnp.abs(g), axis=1)
    u = pam4_k.pam4_quantize_encode(g, scale, bits)
    u_ref = ref.pam4_quantize_encode_ref(g, scale, bits, shape[1])
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u_ref))


@pytest.mark.parametrize("n", [2, 4, 16])
@pytest.mark.parametrize("bits", [4, 8])
def test_pam4_decode_kernel(n, bits):
    shape = (16, 256)
    levels = 2 ** (bits - 1) - 1
    total = jnp.asarray(
        RNG.integers(0, n * 2 * levels, size=shape).astype(np.int32))
    scale = jnp.asarray(RNG.uniform(0.5, 2.0, shape[0]).astype(np.float32))
    out = pam4_k.pam4_decode_dequantize(total, scale, bits, n)
    want = ref.pam4_decode_dequantize_ref(ref.pam4_qmean_ref(total, n),
                                          scale, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("bsz,m,n", [(128, 128, 128), (256, 128, 256),
                                     (128, 256, 384), (384, 512, 128)])
@pytest.mark.parametrize("relu", [True, False])
def test_onn_layer_kernel(bsz, m, n, relu):
    x = jnp.asarray(RNG.normal(size=(bsz, n)).astype(np.float32))
    q, _ = np.linalg.qr(RNG.normal(size=(max(m, n), max(m, n))))
    u = jnp.asarray(q[:m, :n].astype(np.float32))
    d = jnp.asarray(RNG.normal(size=(m,)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(m,)).astype(np.float32))
    y = onn_k.onn_layer(x, u, d, b, relu=relu)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.onn_layer_ref(x, u, d, b, relu)),
                               rtol=1e-4, atol=1e-4)


def _qkv(h, hkv, sq, skv, dtype=jnp.float32, hd=128, hdv=None, b=2):
    """GQA operands q (b, h, sq, hd), k (b, hkv, skv, hd), v (b, hkv,
    skv, hdv) and an output cotangent."""
    hdv = hd if hdv is None else hdv
    shapes = ((b, h, sq, hd), (b, hkv, skv, hd), (b, hkv, skv, hdv),
              (b, h, sq, hdv))
    return [jnp.asarray(RNG.normal(size=s).astype(np.float32)).astype(dtype)
            for s in shapes]


def _mha_ref(q, k, v, causal=True):
    """ref.mha_ref per (batch, head), K/V repeated over each group."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    return jax.vmap(jax.vmap(lambda q, k, v: ref.mha_ref(
        q, k, v, causal=causal)))(q, k, v)


def _grads(fn, q, k, v, do, causal):
    """dq, dk, dv of sum(fn(q, k, v) * do), in f32."""
    def loss(q, k, v):
        o = fn(q, k, v, causal=causal).astype(jnp.float32)
        return jnp.sum(o * do.astype(jnp.float32))
    return [g.astype(jnp.float32)
            for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("h,hkv,sq,skv,causal", [
    (4, 2, 256, 256, True), (4, 2, 128, 512, True), (4, 2, 256, 256, False),
    (4, 2, 512, 512, True), (6, 2, 256, 512, True), (6, 2, 512, 512, False)])
def test_flash_attention_kernel(h, hkv, sq, skv, causal):
    """Forward against the plain reference, f32, GQA; sq < skv aligns the
    causal diagonal at the sequence end."""
    q, k, v, _ = _qkv(h, hkv, sq, skv)
    o = attn_k.flash_attention(q, k, v, causal=causal)
    assert o.shape == q.shape and o.dtype == q.dtype
    assert float(jnp.max(jnp.abs(o - _mha_ref(q, k, v, causal)))) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    """Forward and dq/dk/dv against the plain reference in both operand
    dtypes; the output and the gradients keep the operands' dtype."""
    q, k, v, do = _qkv(6, 2, 256, 256, dtype)
    o = attn_k.flash_attention(q, k, v)
    assert o.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - _mha_ref(
        q, k, v).astype(jnp.float32)))) < tol
    got = _grads(attn_k.flash_attention, q, k, v, do, True)
    want = _grads(_mha_ref, q, k, v, do, True)
    gtol = 1e-4 if dtype == jnp.float32 else 3e-2
    for g, w in zip(got, want):
        assert _rel(g, w) < gtol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,hkv,sq,skv,causal", [
    (4, 2, 256, 256, True), (6, 2, 256, 512, True), (4, 2, 256, 256, False)])
def test_blocked_attention_matches_kernel_math(h, hkv, sq, skv, causal,
                                               dtype):
    """The model's jnp blocked attention (the CPU path and the kernel's
    oracle) and the fused kernel agree, forward and backward."""
    from repro.models.layers import blocked_attention
    q, k, v, do = _qkv(h, hkv, sq, skv, dtype)
    a = blocked_attention(q, k, v, causal=causal, blk_q=128, blk_kv=128)
    b = attn_k.flash_attention(q, k, v, causal=causal)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert _rel(b, a) < tol
    got = _grads(attn_k.flash_attention, q, k, v, do, causal)
    want = _grads(blocked_attention, q, k, v, do, causal)
    for g, w in zip(got, want):
        assert _rel(g, w) < (1e-4 if dtype == jnp.float32 else 3e-2)


@pytest.fixture
def force_kernel():
    """Set ``kernels.attention.FORCE_KERNEL`` for one test, and clear the
    dispatch count."""
    from repro.kernels import ops
    old = attn_k.FORCE_KERNEL
    ops.ATTENTION_PATHS.clear()

    def force(flag):
        attn_k.FORCE_KERNEL = flag
        return ops.ATTENTION_PATHS
    yield force
    attn_k.FORCE_KERNEL = old


@pytest.mark.parametrize("force,shape,path", [
    (None, dict(sq=256, skv=256), "attn_blocked"),         # CPU: platform
    (True, dict(sq=256, skv=256), "attn_fused"),
    (False, dict(sq=256, skv=256), "attn_blocked"),
    (True, dict(sq=256, skv=256, hd=192, hdv=128), "attn_blocked"),  # MLA
    (True, dict(sq=256, skv=256, hd=64), "attn_blocked"),
    (True, dict(sq=200, skv=200), "attn_blocked"),          # off the tiles
])
def test_attention_dispatch(force_kernel, force, shape, path):
    """``ops.flash_attention`` takes the kernel only where it runs and
    the shapes admit it, counts the path at trace time, and both paths
    give the same attention."""
    from repro.kernels import ops
    from repro.models.layers import blocked_attention
    paths = force_kernel(force)
    q, k, v, _ = _qkv(4, 2, b=1, **shape)
    assert jax.default_backend() != "tpu"
    o = ops.flash_attention(q, k, v)
    assert dict(paths) == {path: 1}
    assert _rel(o, blocked_attention(q, k, v)) < 1e-5


@pytest.mark.parametrize("force,path", [(None, "attn_blocked"),
                                        (True, "attn_fused")])
def test_gqa_attention_dispatch(force_kernel, force, path):
    """The model's self-attention goes through the dispatch: blocked on
    the CPU, fused where forced with admissible shapes (head dim 128,
    length 256), and the two agree."""
    import dataclasses
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.models.blocks import gqa_attention
    from repro.models.layers import ShardCtx

    cfg = dataclasses.replace(configs.get_smoke("minitron_4b"),
                              head_dim=128)
    d, hd, h, hkv, t = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads, 256
    rng = np.random.default_rng(3)
    p = {"norm": jnp.ones((d,), jnp.float32),
         "wq": jnp.asarray(rng.normal(size=(d, h * hd)) * 0.1, jnp.float32),
         "wk": jnp.asarray(rng.normal(size=(d, hkv * hd)) * 0.1, jnp.float32),
         "wv": jnp.asarray(rng.normal(size=(d, hkv * hd)) * 0.1, jnp.float32),
         "wo": jnp.asarray(rng.normal(size=(h * hd, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(1, t, d)), jnp.float32)
    mesh = jax.make_mesh((1,), ("model",))

    def run():
        return jax.shard_map(
            lambda p_, x_: gqa_attention(ShardCtx(), cfg, p_, x_,
                                         jnp.arange(t))[0],
            mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), p), P()),
            out_specs=P(), check_vma=False)(p, x)

    paths = force_kernel(False)
    want = run()
    paths.clear()
    force_kernel(force)
    got = run()
    assert dict(paths) == {path: 1}
    assert _rel(got, want) < 1e-5
