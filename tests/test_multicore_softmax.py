"""Multi-core softmax (paper §III-B2): sharded == full, tree == collective."""
from tests._multidevice import run_with_devices


def test_sharded_softmax_matches_full():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core.multicore_softmax import (sharded_softmax,
                                                  sharded_softmax_tree)
        from repro.core.lut_softmax import lut_softmax

        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32) * 5)

        f = shard_map(
            functools.partial(sharded_softmax, axis_name="model"),
            mesh=mesh, in_specs=P(None, "model"), out_specs=P(None, "model"))
        got = np.asarray(f(x))
        want = np.asarray(lut_softmax(x))
        np.testing.assert_allclose(got, want, atol=3e-6)

        g = shard_map(
            functools.partial(sharded_softmax_tree, axis_name="model"),
            mesh=mesh, in_specs=P(None, "model"), out_specs=P(None, "model"))
        got_tree = np.asarray(g(x))
        # the explicit ppermute butterfly is step-for-step equivalent
        np.testing.assert_allclose(got_tree, got, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_tree_allreduce_is_logn():
    """The butterfly must use exactly log2(n) ppermute rounds."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core.multicore_softmax import tree_allreduce

        mesh = make_mesh((8,), ("m",))
        f = shard_map(
            lambda x: tree_allreduce(x, jnp.add, "m"),
            mesh=mesh, in_specs=P("m"), out_specs=P("m"))
        x = jnp.arange(8.0)
        assert float(f(x)[0]) == 28.0          # Σ 0..7 on every shard
        hlo = jax.jit(f).lower(x).as_text()
        n_permutes = hlo.count("collective_permute")
        assert n_permutes >= 3, n_permutes      # log2(8) rounds
        print("OK", n_permutes)
    """)
    assert "OK" in out
