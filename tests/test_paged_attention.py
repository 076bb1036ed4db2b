"""kernels.paged_attention: interpreted-kernel parity against the
gather oracle (paged_gather -> decode_attention), null-page invariance
under garbage pool contents, kv_dtype storage tolerance, dispatch
policy, and the gqa_decode_paged off-TPU fallback equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention as pk
from repro.models.layers import ShardCtx, decode_attention, paged_gather


def _case(b=4, h=4, hkv=2, ps=4, nb=3, hd=8, n_pages=None, seed=0,
          dtype=jnp.float32):
    """Random pool + per-slot page tables + a mix of lengths (0, mid-page,
    page-aligned, full allocation).  Pages beyond a slot's length point
    at the null page 0, which holds zeros, like the engine maintains."""
    rng = np.random.default_rng(seed)
    n_pages = n_pages or 1 + b * nb
    q = jnp.asarray(rng.normal(size=(b, h, 1, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd)), dtype)
    cap = nb * ps
    base = [0, ps - 1, ps, cap]                         # the edge cases
    lengths = np.asarray((base * b)[:b], np.int32)
    table = np.zeros((b, nb), np.int32)
    for i in range(b):
        used = -(-int(lengths[i]) // ps)
        table[i, :used] = 1 + i * nb + np.arange(used)
    # null page is all-zero (the pool invariant write_prompts maintains)
    kp = kp.at[0].set(0)
    vp = vp.at[0].set(0)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)


def _oracle(q, kp, vp, table, lengths):
    return decode_attention(ShardCtx(), q, paged_gather(kp, table),
                            paged_gather(vp, table), lengths)


# ------------------------------------------------------------- parity
@pytest.mark.parametrize("b,h,hkv,ps,nb,hd", [
    (4, 4, 2, 4, 3, 8),      # GQA rep=2, the serving smoke shape family
    (2, 4, 4, 8, 2, 16),     # MHA rep=1
    (8, 8, 2, 4, 4, 8),      # rep=4, full occupancy bucket
    (1, 2, 1, 16, 1, 32),    # single slot, single page
])
def test_kernel_matches_gather_oracle(b, h, hkv, ps, nb, hd):
    """Interpreted kernel vs the gather path across shapes and lengths
    (0, mid-page, page-aligned, full): equal to float associativity of
    the online softmax."""
    q, kp, vp, table, lengths = _case(b, h, hkv, ps, nb, hd)
    got = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    ref = _oracle(q, kp, vp, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_kernel_ignores_null_page_garbage():
    """Poisoning the null page changes NOTHING for any slot with >= 1
    valid position (all the engine ever attends — pad rows get valid
    count 1 at position 0): masking is by position-vs-length, never by
    trusting pool contents.  Holds for the kernel and the gather oracle
    alike.  (A length-0 row is all-masked -> uniform weights -> mean of
    its pages; both paths produce the same garbage and nothing reads it.)"""
    q, kp, vp, table, lengths = _case(seed=3)
    live = np.asarray(lengths) > 0
    clean_k = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    clean_o = _oracle(q, kp, vp, table, lengths)
    kp = kp.at[0].set(1e4)
    vp = vp.at[0].set(-1e4)
    dirty_k = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    dirty_o = _oracle(q, kp, vp, table, lengths)
    np.testing.assert_array_equal(np.asarray(dirty_k)[live],
                                  np.asarray(clean_k)[live])
    np.testing.assert_array_equal(np.asarray(dirty_o)[live],
                                  np.asarray(clean_o)[live])


def test_kernel_masks_partial_page_tail():
    """Stale garbage in the tail of a slot's LAST page (positions >=
    length, same page) contributes exactly nothing."""
    q, kp, vp, table, lengths = _case(seed=4)
    clean = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    # slot 1 has length ps-1: poison the final position of its only page
    pg = int(table[1, 0])
    kp = kp.at[pg, :, -1].set(1e4)
    vp = vp.at[pg, :, -1].set(-1e4)
    dirty = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(dirty[1]), np.asarray(clean[1]))


def test_kernel_bf16_pool_within_storage_tolerance():
    """bf16 page storage vs f32 (ServeConfig.kv_dtype): same f32
    accumulate, the only loss is the bf16 rounding of the stored K/V —
    tolerance-gated at bf16 precision, and the f32 kernel result stays
    tight against the f32 oracle."""
    q, kp, vp, table, lengths = _case(seed=5, hd=16)
    ref = pk.paged_attention(q, kp, vp, table, lengths, interpret=True)
    got = pk.paged_attention(q, kp.astype(jnp.bfloat16),
                             vp.astype(jnp.bfloat16), table, lengths,
                             interpret=True)
    assert got.dtype == q.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)
    # and the bf16 oracle agrees with the bf16 kernel much tighter than
    # that storage error (both consume the same rounded pages)
    ref16 = _oracle(q, kp.astype(jnp.bfloat16).astype(jnp.float32),
                    vp.astype(jnp.bfloat16).astype(jnp.float32),
                    table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref16),
                               atol=2e-6, rtol=2e-6)


# ----------------------------------------------------------- dispatch
def test_use_kernel_dispatch_policy():
    """Explicit flag > FORCE_KERNEL hook > platform (CPU CI: False)."""
    assert pk.use_kernel(True) and not pk.use_kernel(False)
    assert pk.use_kernel() == (jax.default_backend() == "tpu")
    old = pk.FORCE_KERNEL
    try:
        pk.FORCE_KERNEL = True
        assert pk.use_kernel() and not pk.use_kernel(False)
        pk.FORCE_KERNEL = False
        assert not pk.use_kernel() and pk.use_kernel(True)
    finally:
        pk.FORCE_KERNEL = old


def test_gqa_decode_paged_backend_fallback_is_bit_exact():
    """Off-TPU, backend='paged' dispatches to the gather math: bitwise
    equal to backend='gather' (the property the CPU engine parity tests
    lean on); FORCE_KERNEL swaps in the interpreted kernel, which agrees
    to tolerance only."""
    from repro import configs
    from repro.models.blocks import gqa_decode_paged

    cfg = configs.get_smoke("minitron_4b")
    ctx = ShardCtx()
    rng = np.random.default_rng(6)
    d, hd = cfg.d_model, cfg.hd
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    b, ps, nb = 2, 4, 2
    n_pages = 1 + b * nb
    p = {"norm": jnp.ones((d,), jnp.float32),
         "wq": jnp.asarray(rng.normal(size=(d, h * hd)) * 0.1, jnp.float32),
         "wk": jnp.asarray(rng.normal(size=(d, hkv * hd)) * 0.1, jnp.float32),
         "wv": jnp.asarray(rng.normal(size=(d, hkv * hd)) * 0.1, jnp.float32),
         "wo": jnp.asarray(rng.normal(size=(h * hd, d)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(b, 1, d)), jnp.float32)
    pool = {"k": jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd)),
                             jnp.float32).at[0].set(0),
            "v": jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd)),
                             jnp.float32).at[0].set(0)}
    table = jnp.asarray(np.arange(1, 1 + b * nb).reshape(b, nb), jnp.int32)
    lengths = jnp.asarray([3, 5], jnp.int32)

    # sp_out psums over the 'model' axis -> bind a 1-device mesh
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",))
    kv_specs = {"k": P(), "v": P()}

    def run(backend):
        def f(p_, x_, kv_):
            return gqa_decode_paged(ctx, cfg, p_, x_, lengths, kv_, table,
                                    backend=backend)
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), p), P(), kv_specs),
            out_specs=(P(), kv_specs), check_vma=False)(p, x, pool)

    out_g, kv_g = run("gather")
    out_p, kv_p = run("paged")
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_p))
    np.testing.assert_array_equal(np.asarray(kv_g["k"]),
                                  np.asarray(kv_p["k"]))
    old = pk.FORCE_KERNEL
    try:
        pk.FORCE_KERNEL = True
        out_k, _ = run("paged")
    finally:
        pk.FORCE_KERNEL = old
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_g),
                               atol=1e-5, rtol=1e-5)
    assert not np.array_equal(np.asarray(out_k), np.asarray(out_g))
