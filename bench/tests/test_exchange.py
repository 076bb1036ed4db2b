"""The reference's gradient exchanges.  OptINC's average is written in
the reference from the paper's equations alone; here it is held against
the program's own optinc backend, on four host CPU devices, and must
agree bit for bit on the same inputs."""
import json
import os
import subprocess
import sys

import numpy as np

from bench import common, reference


def program_against_reference():
    """Run in a process of its own, which makes four devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.collectives import SyncConfig
    from repro.collectives.backends import OptincBackend

    rng = np.random.default_rng(0)
    n = 3 * 2048 + 517                 # a ragged last block
    g = rng.standard_normal((4, n)) * np.exp(rng.standard_normal((4, 1)))
    g = g.astype(np.float32)
    g[:, 2048:4096] = 0.0              # a block that is zero on every chip
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    cfg = SyncConfig(mode="optinc", bits=8, block=2048, axes=("data",))
    f = jax.shard_map(
        lambda x: OptincBackend().sync(x[0], cfg, None)[0][None], mesh=mesh,
        in_specs=P("data"), out_specs=P("data"), check_vma=False)
    prog = np.asarray(jax.jit(f)(jnp.asarray(g)))
    trees = [{"a": jnp.asarray(g[c][:5000]), "b": jnp.asarray(g[c][5000:])}
             for c in range(4)]
    out = reference.exchange(trees, reference.Exchange("optinc", 8, 2048),
                             jax.devices()[0])
    ref = np.concatenate([np.asarray(out["a"]), np.asarray(out["b"])])
    print(json.dumps({"same_on_every_chip": all(
        np.array_equal(prog[0], p) for p in prog),
        "bit_exact": bool(np.array_equal(prog[0], ref)),
        "max_abs_diff": float(np.max(np.abs(prog[0] - ref))),
        "trees_left": len(trees)}))


def test_optinc_reference_matches_the_program_bit_for_bit():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(common.SRC),
                                           str(common.ROOT)]))
    p = subprocess.run(
        [sys.executable, "-c", "from bench.tests.test_exchange import "
         "program_against_reference as f; f()"],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"same_on_every_chip": True, "bit_exact": True,
                   "max_abs_diff": 0.0, "trees_left": 0}


def test_mean_and_none_exchanges():
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    trees = [{"a": jnp.full((3,), float(c), jnp.float32)} for c in range(4)]
    mean = reference.exchange(list(trees), reference.MEAN, dev)
    assert np.allclose(np.asarray(mean["a"]), 1.5)
    solo = reference.exchange(list(trees), reference.Exchange("none"), dev)
    assert np.array_equal(np.asarray(solo["a"]), np.zeros(3))
