"""Ring attention + distributed decode vs the single-device oracle."""
from tests._multidevice import run_with_devices


def test_ring_attention_matches_naive():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core.ring_attention import ring_attention
        from repro.core.streaming_attention import naive_attention

        mesh = make_mesh((4,), ("sp",))
        rng = np.random.default_rng(0)
        B, Hq, Hkv, L, D = 2, 4, 2, 64, 16
        q = jnp.asarray(rng.normal(size=(B, Hq, L, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))

        for kw in (dict(causal=True), dict(causal=True, window=24),
                   dict(causal=False, cap=25.0)):
            f = shard_map(
                functools.partial(ring_attention, axis_name="sp", **kw),
                mesh=mesh,
                in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                          P(None, None, "sp")),
                out_specs=P(None, None, "sp"))
            got = np.asarray(f(q, k, v))
            want = np.asarray(naive_attention(q, k, v, exp_mode="lut", **kw))
            np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_decode_matches_naive():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core.ring_attention import distributed_decode_attention
        from repro.core.streaming_attention import naive_attention

        mesh = make_mesh((8,), ("sp",))
        rng = np.random.default_rng(1)
        B, Hq, Hkv, L, D = 2, 4, 4, 128, 16
        kv_len = 100
        q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))

        f = shard_map(
            functools.partial(distributed_decode_attention, axis_name="sp",
                              kv_len=jnp.int32(kv_len)),
            mesh=mesh,
            in_specs=(P(), P(None, None, "sp"), P(None, None, "sp")),
            out_specs=P())
        got = np.asarray(f(q, k, v))
        want = np.asarray(naive_attention(
            q, k, v, causal=True, q_offset=kv_len - 1, kv_len=kv_len,
            exp_mode="lut"))
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out
