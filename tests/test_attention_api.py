"""Attention-backend registry: dispatch, resolution, backend equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention_api import (AttentionCall, attention,
                                      backend_for_config, describe_call,
                                      get_backend, list_backends,
                                      register_backend, resolve_backend,
                                      _REGISTRY)


def qkv(rng, b=2, hq=4, hkv=2, lq=24, lkv=24, d=16):
    q = jnp.asarray(rng.normal(size=(b, hq, lq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, lkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, lkv, d)).astype(np.float32))
    return q, k, v


# --------------------------------------------------------- registry basics --

def test_builtin_backends_registered():
    assert {"naive", "naive_decode", "jnp", "pallas", "ring"} <= set(
        list_backends())


def test_unknown_backend_raises():
    rng = np.random.default_rng(0)
    q, k, v = qkv(rng)
    with pytest.raises(KeyError, match="unknown attention backend"):
        attention(q, k, v, backend="flash3")


def test_register_custom_backend_dispatches():
    @register_backend("all_ones_test", supports=lambda call: True)
    def ones_backend(q, k, v, **kw):
        return jnp.ones_like(q)
    try:
        rng = np.random.default_rng(0)
        q, k, v = qkv(rng)
        out = attention(q, k, v, backend="all_ones_test")
        assert bool(jnp.all(out == 1.0))
    finally:
        del _REGISTRY["all_ones_test"]


def test_backend_for_config_legacy_mapping():
    assert backend_for_config("auto", "streaming") == "auto"
    assert backend_for_config("auto", "naive") == "naive"
    assert backend_for_config("auto", "pallas") == "pallas"
    assert backend_for_config("jnp", "naive") == "jnp"   # explicit wins


# ------------------------------------------------------------- resolution --

def _call(**kw):
    base = dict(lq=16, lkv=16, platform="cpu", static_lengths=True,
                has_kv_pos=False, inside_shard_map=False)
    base.update(kw)
    return AttentionCall(**base)


def test_auto_resolution_cpu():
    # multi-row on CPU → streaming jnp; single row → naive O(L) fast path
    assert resolve_backend("auto", _call()).name == "jnp"
    assert resolve_backend("auto", _call(lq=1)).name == "naive_decode"
    # inside shard_map only the ring backend applies
    assert resolve_backend("auto", _call(inside_shard_map=True)).name == "ring"


def test_auto_resolution_tpu_prefers_pallas():
    assert resolve_backend("auto", _call(platform="tpu")).name == "pallas"
    # dynamic lengths / ring positions disqualify the kernel
    assert resolve_backend(
        "auto", _call(platform="tpu", static_lengths=False)).name == "jnp"
    assert resolve_backend(
        "auto", _call(platform="tpu", has_kv_pos=True)).name == "jnp"


def test_explicit_unsupported_raises_and_fallback_degrades():
    spec_call = _call(has_kv_pos=True)
    with pytest.raises(ValueError, match="does not support"):
        resolve_backend("pallas", spec_call)
    assert resolve_backend("pallas", spec_call, fallback=True).name == "jnp"


def test_describe_call_static_vs_traced():
    rng = np.random.default_rng(0)
    q, k, _ = qkv(rng)
    assert describe_call(q, k, q_offset=0, kv_len=8).static_lengths
    traced = jnp.asarray(3, jnp.int32)
    assert not describe_call(q, k, q_offset=traced).static_lengths


# ------------------------------------------- backend equivalence vs naive --

CFGS = [dict(causal=True),
        dict(causal=False),
        dict(causal=True, window=9),
        dict(causal=True, cap=20.0),
        dict(causal=True, window=7, cap=15.0)]


@pytest.mark.parametrize("kw", CFGS)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_backends_match_naive(backend, kw, rng):
    q, k, v = qkv(rng)
    want = np.asarray(attention(q, k, v, backend="naive", exp_mode="lut",
                                **kw))
    got = np.asarray(attention(q, k, v, backend=backend, block_k=8,
                               exp_mode="lut", **kw))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("kw", CFGS[:3])
def test_decode_row_matches_naive(kw, rng):
    """lq=1 auto path (naive_decode) == naive with a q_offset/kv_len cache."""
    q, k, v = qkv(rng, lq=1, lkv=32)
    want = np.asarray(attention(q, k, v, backend="naive", q_offset=20,
                                kv_len=21, **kw))
    got = np.asarray(attention(q, k, v, backend="auto", q_offset=20,
                               kv_len=21, **kw))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_ring_backend_via_shard_map(rng):
    """The "ring" backend dispatches inside shard_map (1-device mesh here;
    the 4/8-chip equivalence lives in test_ring_attention.py)."""
    import functools
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    q, k, v = qkv(rng)
    mesh = make_mesh((1,), ("sp",))
    f = shard_map(
        functools.partial(attention, backend="ring", axis_name="sp",
                          causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                  P(None, None, "sp")),
        out_specs=P(None, None, "sp"))
    got = np.asarray(f(q, k, v))
    want = np.asarray(attention(q, k, v, backend="naive", causal=True,
                                exp_mode="lut"))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


def test_pallas_backend_grad_matches_jnp(rng):
    """Kernel forward + jnp flash backward: grads equal the jnp backend's."""
    q, k, v = qkv(rng, b=1, lq=16, lkv=16)

    def loss(backend):
        def f(q, k, v):
            return jnp.sum(attention(q, k, v, backend=backend, causal=True,
                                     block_k=8) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gp = loss("pallas")
    gs = loss("jnp")
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_model_config_backend_threading(rng):
    """cfg.attn_backend reaches the layers: pinning "naive" vs "jnp" both
    run, agree, and a bogus name fails fast at build_model."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("deepseek-7b-smoke")
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    ln, _ = build_model(cfg.replace(attn_backend="naive")).loss(params, batch)
    lj, _ = build_model(cfg.replace(attn_backend="jnp")).loss(params, batch)
    assert abs(float(ln) - float(lj)) < 1e-3
    with pytest.raises(KeyError, match="unknown attention backend"):
        build_model(cfg.replace(attn_backend="flashinfer"))
