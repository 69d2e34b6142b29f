"""Paged-attention backend: equivalence vs the contiguous oracles over
shuffled page tables / ragged lengths / GQA ratios / int8 pools, registry
resolution, and the no-gathered-view graph guarantee."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI image without hypothesis: seeded fallback
    from tests._hypothesis_stub import given, settings, st

from repro.core.attention_api import (AttentionCall, attention,
                                      resolve_backend)
from repro.core.streaming_attention import quantize_kv_rows
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_reference)


def make_pool(rng, n, hkv, ps, d):
    return (jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32)))


def shuffled_tables(rng, b, p, n):
    """Each lane's pages drawn without replacement, in random pool order."""
    return jnp.asarray(np.stack([rng.permutation(n)[:p] for _ in range(b)]),
                       jnp.int32)


def gather_view(pool, tbl):
    """(N, Hkv, ps, D) + (B, P) → the contiguous (B, Hkv, P·ps, D) view the
    in-place path exists to avoid — used here only as the oracle input."""
    out = jnp.moveaxis(jnp.take(pool, tbl, axis=0), 1, 2)
    s = out.shape
    return out.reshape(s[0], s[1], s[2] * s[3], *s[4:])


def oracle(backend, q, kg, vg, lens, **kw):
    """Per-lane contiguous-backend attention at each lane's own length."""
    outs = []
    for i in range(q.shape[0]):
        li = int(lens[i])
        outs.append(attention(q[i:i + 1], kg[i:i + 1], vg[i:i + 1],
                              backend=backend, causal=True,
                              q_offset=li - 1, kv_len=li, exp_mode="lut",
                              **kw))
    return np.concatenate([np.asarray(o) for o in outs], axis=0)


# ------------------------------------------------------------- equivalence --

@settings(max_examples=12, deadline=None)
@given(st.integers(1, 4),              # GQA group size
       st.integers(1, 3),              # batch lanes
       st.sampled_from([4, 8, 16]),    # page size
       st.integers(2, 5),              # table width (pages per lane)
       st.integers(0, 10_000))         # seed
def test_paged_matches_contiguous_backends(group, b, ps, p, seed):
    """Reference paged attention == naive/jnp on the gathered view, for
    shuffled tables, ragged per-lane lengths and every GQA packing."""
    rng = np.random.default_rng(seed)
    hkv, d = 2, 16
    hq = hkv * group
    n = p * b + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray(rng.integers(1, p * ps + 1, size=b), jnp.int32)

    got = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens,
                                               exp_mode="lut"))
    kg, vg = gather_view(kp, tbl), gather_view(vp, tbl)
    for backend in ("naive", "jnp"):
        want = oracle(backend, q, kg, vg, lens)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4,
                                   err_msg=backend)


@pytest.mark.parametrize("block_pages", [1, 2, 8])
@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([4, 8]), st.integers(0, 10_000))
def test_paged_kernel_interpret_matches_reference(block_pages, group, ps,
                                                  seed):
    """The Pallas kernel (interpret mode) == the jnp page-block reference,
    walking ``block_pages`` pages per grid step (8 > the table width)."""
    rng = np.random.default_rng(seed)
    b, hkv, d, p = 2, 2, 16, 3
    n = p * b + 2
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, 1, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray(rng.integers(1, p * ps + 1, size=b), jnp.int32)

    ref = paged_attention_reference(q, kp, vp, tbl, lens, exp_mode="lut")
    ker = paged_attention(q, kp, vp, tbl, lens, exp_mode="lut",
                          block_pages=block_pages, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_paged_int8_pool_close_to_float(rng):
    """INT8 pools (per-row scales, dequantised per page block) track the
    float path within quantisation error, on both reference and kernel."""
    b, hq, hkv, d, ps, p = 2, 4, 2, 32, 8, 4
    n = p * b + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray([13, 29], jnp.int32)

    def quant(pool):
        qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
        return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)

    kq, ks = quant(kp)
    vq, vs = quant(vp)
    want = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens))
    for impl in (paged_attention_reference,
                 lambda *a, **kw: paged_attention(*a, **kw, interpret=True)):
        got = np.asarray(impl(q, kq, vq, tbl, lens,
                              k_scale=ks, v_scale=vs))
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 0.02, rel


def test_paged_window_and_softcap(rng):
    """Sliding-window + logit-softcap masking agree with the naive oracle."""
    b, hq, hkv, d, ps, p = 2, 4, 2, 16, 8, 4
    n = p * b
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray([9, 27], jnp.int32)
    kw = dict(window=7, cap=15.0)

    got = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens, **kw))
    want = oracle("naive", q, gather_view(kp, tbl), gather_view(vp, tbl),
                  lens, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_paged_via_attention_api(rng):
    """attention(page_table=...) resolves to the paged backend and matches
    calling the kernel module directly."""
    b, hq, hkv, d, ps, p = 2, 4, 2, 16, 8, 3
    n = 8
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray([5, 20], jnp.int32)

    via_api = attention(q, kp, vp, backend="auto", causal=True,
                        kv_len=lens, page_table=tbl)
    direct = paged_attention(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(via_api), np.asarray(direct),
                               atol=0, rtol=0)


# ------------------------------------------------------- chunked prefill --

def chunk_oracle(backend, q, kg, vg, lens, **kw):
    """Per-lane contiguous-backend attention for a query *chunk* whose rows
    end at each lane's live length (q_offset = len - Lq)."""
    lq = q.shape[2]
    outs = []
    for i in range(q.shape[0]):
        li = int(lens[i])
        outs.append(attention(q[i:i + 1], kg[i:i + 1], vg[i:i + 1],
                              backend=backend, causal=True,
                              q_offset=li - lq, kv_len=li, exp_mode="lut",
                              **kw))
    return np.concatenate([np.asarray(o) for o in outs], axis=0)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3),              # GQA group size
       st.integers(1, 6),              # chunk length Lq
       st.sampled_from([4, 8]),        # page size
       st.integers(0, 10_000))         # seed
def test_chunked_prefill_matches_contiguous_backends(group, lq, ps, seed):
    """Multi-row paged queries (the chunked-prefill path) == naive/jnp on
    the gathered view with the same causal intra-chunk mask, over shuffled
    tables, ragged per-lane lengths and GQA packings."""
    rng = np.random.default_rng(seed)
    b, hkv, d, p = 2, 2, 16, 4
    hq = hkv * group
    n = p * b + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, lq, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray(rng.integers(lq, p * ps + 1, size=b), jnp.int32)

    got = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens,
                                               exp_mode="lut"))
    kg, vg = gather_view(kp, tbl), gather_view(vp, tbl)
    for backend in ("naive", "jnp"):
        want = chunk_oracle(backend, q, kg, vg, lens)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4,
                                   err_msg=backend)


@pytest.mark.parametrize("block_pages", [1, 2, 8])
@settings(max_examples=6, deadline=None)
@given(st.integers(1, 3), st.integers(2, 5), st.integers(0, 10_000))
def test_chunked_kernel_interpret_matches_reference(block_pages, group, lq,
                                                    seed):
    """The Pallas kernel (interpret mode) == the jnp reference for
    multi-row chunks — the per-row causal bound lives in both, whatever
    the page block."""
    rng = np.random.default_rng(seed)
    b, hkv, d, ps, p = 2, 2, 16, 8, 3
    n = p * b + 2
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, lq, d))
                    .astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray(rng.integers(lq, p * ps + 1, size=b), jnp.int32)

    ref = paged_attention_reference(q, kp, vp, tbl, lens, exp_mode="lut")
    ker = paged_attention(q, kp, vp, tbl, lens, exp_mode="lut",
                          block_pages=block_pages, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_chunked_window_and_int8(rng):
    """Sliding window masks per query row, and int8 pools track float —
    on the chunked path specifically."""
    b, hq, hkv, d, ps, p, lq = 2, 4, 2, 32, 8, 4, 5
    n = p * b + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q = jnp.asarray(rng.normal(size=(b, hq, lq, d)).astype(np.float32))
    tbl = shuffled_tables(rng, b, p, n)
    lens = jnp.asarray([13, 29], jnp.int32)

    got = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens,
                                               window=7, cap=15.0))
    want = chunk_oracle("naive", q, gather_view(kp, tbl),
                        gather_view(vp, tbl), lens, window=7, cap=15.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)

    def quant(pool):
        qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
        return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)

    kq, ks = quant(kp)
    vq, vs = quant(vp)
    base = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens))
    for impl in (paged_attention_reference,
                 lambda *a, **kw: paged_attention(*a, **kw, interpret=True)):
        got = np.asarray(impl(q, kq, vq, tbl, lens, k_scale=ks, v_scale=vs))
        rel = np.linalg.norm(got - base) / np.linalg.norm(base)
        assert rel < 0.02, rel


# --------------------------------------------------------------- registry --

def _call(**kw):
    base = dict(lq=1, lkv=8, platform="cpu", static_lengths=False,
                has_kv_pos=False, inside_shard_map=False,
                has_page_table=True)
    base.update(kw)
    return AttentionCall(**base)


def test_resolution_paged_calls_only_reach_paged():
    assert resolve_backend("auto", _call()).name == "paged"
    # chunked prefill (multi-row queries with a page table) resolves too
    assert resolve_backend("auto", _call(lq=4)).name == "paged"
    # contiguous backends refuse pool+page-table calls even explicitly
    for name in ("naive", "naive_decode", "jnp", "pallas"):
        with pytest.raises(ValueError, match="does not support"):
            resolve_backend(name, _call())


def test_resolution_contiguous_calls_never_pick_paged():
    call = _call(has_page_table=False, static_lengths=True)
    assert resolve_backend("auto", call).name != "paged"
    with pytest.raises(ValueError, match="does not support"):
        resolve_backend("paged", call)
