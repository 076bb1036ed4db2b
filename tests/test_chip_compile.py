"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what Mosaic would refuse on the chip
(block shapes off the (8, 128) tiling, casts and ops it cannot lower) —
what interpret mode never sees.  Each test asserts the kernel is in the
compiled program (``tpu_custom_call``).

The topology is described inside the ``topo`` fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.  JAX's persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import flash_attention
from repro.kernels.mesh_scan import mesh_scan_blocks
from repro.kernels.paged_attention import paged_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("theta_std", [0.0, 0.02])
@pytest.mark.parametrize("blocks", [1, 4])
def test_mesh_scan_blocks_compiles_for_v5e(one_chip, blocks, theta_std):
    """The fused MZI-mesh kernel at a scenario-1 width (256 wires, depth
    2m), B stacked programs sharing a 4096-row batch, with and without
    in-kernel phase-noise draws."""
    m, depth, batch = 256, 512, 4096

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def apply(signs, perm, ca, sa, x, post, seeds):
        return mesh_scan_blocks(signs, perm, ca, sa, x, post_scale=post,
                                interpret=False, theta_std=theta_std,
                                seeds=seeds if theta_std else None)

    hlo = compiled_text(apply, sds((blocks, m)),
                        sds((blocks, depth, m), jnp.int32),
                        sds((blocks, depth, m)), sds((blocks, depth, m)),
                        sds((batch, m)), sds((blocks, m)),
                        sds((blocks,), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rep,hd", [(3, 128), (1, 48)],
                         ids=["minitron_gqa", "paper_llama"])
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_compiles_for_v5e(one_chip, pool_dtype, rep, hd):
    """The paged decode kernel over a page-16 pool: 8 slots, 32-page
    tables (max_seq 512), 8 kv heads."""
    b, hkv, page, nb = 8, 8, 16, 32
    n_pages = 1 + b * nb

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = compiled_text(functools.partial(paged_attention, interpret=False),
                        sds((b, hkv * rep, 1, hd), jnp.float32),
                        sds((n_pages, hkv, page, hd), pool_dtype),
                        sds((n_pages, hkv, page, hd), pool_dtype),
                        sds((b, nb), jnp.int32), sds((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b,h,hkv,s,grad", [
    (2, 24, 8, 4096, True), (4, 56, 8, 1024, False)],
    ids=["minitron_train", "dscoder_prefill"])
def test_flash_attention_compiles_for_v5e(one_chip, b, h, hkv, s, grad):
    """The fused attention at the training cell's shape, forward and
    backward (the forward kernel and both backward kernels), and at a
    serving prefill's, forward only; bf16 operands, causal."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    hlo = compiled_text(fn, sds((b, h, s, 128)), sds((b, hkv, s, 128)),
                        sds((b, hkv, s, 128)))
    assert hlo.count("tpu_custom_call") >= (3 if grad else 1)
