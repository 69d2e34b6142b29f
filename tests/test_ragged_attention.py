"""Varlen (ragged) paged attention: the packed-token-stream kernel proven
against BOTH oracles — the contiguous backends on the gathered view (per
lane, at each token's own causal bound) and the padded-paged chunk kernel
(the PR-3 step the ragged path replaces) — over ragged per-lane lengths,
GQA ratios, int8 pools and shuffled page tables; plus the ragged calling
convention through the attention-API registry."""
import jax
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
                                           paged_attention_varlen,
                                           paged_attention_varlen_reference,
                                           q_block_layout,
                                           validate_cu_seqlens,
                                           varlen_positions)
from tests._jaxpr import iter_eqns


def make_pool(rng, n, hkv, ps, d):
    return (jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32)))


def gather_view(pool, tbl):
    """(N, Hkv, ps, D) + (S, P) → the contiguous (S, Hkv, P·ps, D) view the
    ragged path exists to avoid — used here only as the oracle input."""
    out = jnp.moveaxis(jnp.take(pool, tbl, axis=0), 1, 2)
    s = out.shape
    return out.reshape(s[0], s[1], s[2] * s[3], *s[4:])


def make_stream(rng, *, lanes, hq, d, ps, p, n):
    """A random packed stream: per-lane chunk lengths 1..4 at ragged live
    lengths, shuffled per-lane page tables → every varlen input array."""
    nq = rng.integers(1, 5, size=lanes)                   # chunk per lane
    lens = np.array([int(rng.integers(nq[i], p * ps + 1))
                     for i in range(lanes)])              # live after chunk
    cu = np.concatenate([[0], np.cumsum(nq)]).astype(np.int32)
    t = int(cu[-1])
    lane_tbl = np.stack([rng.permutation(n)[:p] for _ in range(lanes)])
    q = jnp.asarray(rng.normal(size=(t, hq, d)).astype(np.float32))
    q_pos = varlen_positions(cu, lens)
    token_tbl = lane_tbl[np.repeat(np.arange(lanes), nq)]  # (T, P)
    return q, jnp.asarray(token_tbl, jnp.int32), jnp.asarray(q_pos), \
        cu, jnp.asarray(lane_tbl, jnp.int32), lens, nq


def contiguous_oracle(backend, q, cu, lane_tbl, lens, kp, vp, **kw):
    """Per-lane contiguous attention on the gathered view: lane i's chunk
    rows at q_offset = len_i - nq_i — concatenated back into the stream."""
    kg, vg = gather_view(kp, lane_tbl), gather_view(vp, lane_tbl)
    outs = []
    for i in range(len(lens)):
        nq = int(cu[i + 1] - cu[i])
        li = int(lens[i])
        qi = jnp.moveaxis(q[cu[i]:cu[i + 1]], 0, 1)[None]  # (1, Hq, nq, D)
        o = attention(qi, kg[i:i + 1], vg[i:i + 1], backend=backend,
                      causal=True, q_offset=li - nq, kv_len=li,
                      exp_mode="lut", **kw)
        outs.append(np.moveaxis(np.asarray(o[0]), 0, 1))   # (nq, Hq, D)
    return np.concatenate(outs, axis=0)


def padded_paged_oracle(q, cu, lane_tbl, lens, kp, vp, **kw):
    """The PR-3 padded chunk kernel, lane by lane: q (1, Hq, nq, D) at
    kv_len = len_i through the lane's table row."""
    outs = []
    for i in range(len(lens)):
        qi = jnp.moveaxis(q[cu[i]:cu[i + 1]], 0, 1)[None]
        o = paged_attention(qi, kp, vp, lane_tbl[i:i + 1],
                            jnp.asarray([int(lens[i])], jnp.int32),
                            exp_mode="lut", **kw)
        outs.append(np.moveaxis(np.asarray(o[0]), 0, 1))
    return np.concatenate(outs, axis=0)


# ------------------------------------------------------------- equivalence --

@settings(max_examples=12, deadline=None)
@given(st.integers(1, 4),              # GQA group size
       st.integers(1, 4),              # lanes packed into the stream
       st.sampled_from([4, 8, 16]),    # page size
       st.integers(2, 5),              # table width (pages per lane)
       st.integers(0, 10_000))         # seed
def test_varlen_matches_contiguous_backends(group, lanes, ps, p, seed):
    """Varlen reference == naive/jnp on the gathered view at every token's
    own causal bound, for shuffled tables, ragged lane lengths, ragged
    chunk lengths and every GQA packing."""
    rng = np.random.default_rng(seed)
    hkv, d = 2, 16
    hq = hkv * group
    n = p * lanes + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, lane_tbl, lens, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)

    got = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, cu_seqlens=cu, exp_mode="lut"))
    for backend in ("naive", "jnp"):
        want = contiguous_oracle(backend, q, cu, lane_tbl, lens, kp, vp)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4,
                                   err_msg=backend)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.sampled_from([4, 8]),
       st.integers(0, 10_000))
def test_varlen_matches_padded_paged_oracle(group, lanes, ps, seed):
    """Varlen == the padded-paged chunk kernel (the step it replaces) on
    the same pools/tables/positions — the flattening changes the batch
    layout, never a number."""
    rng = np.random.default_rng(seed)
    hkv, d, p = 2, 16, 3
    hq = hkv * group
    n = p * lanes + 2
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, lane_tbl, lens, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)

    got = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, exp_mode="lut"))
    want = padded_paged_oracle(q, cu, lane_tbl, lens, kp, vp)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("block_pages", [1, 2, 8])
@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([4, 8]), st.integers(0, 10_000))
def test_varlen_kernel_interpret_matches_reference(block_pages, group, ps,
                                                   seed):
    """The Pallas kernel (interpret mode, grid over tokens) == the jnp
    varlen reference, at every page block."""
    rng = np.random.default_rng(seed)
    lanes, hkv, d, p = 3, 2, 16, 3
    n = p * lanes + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, _, _, _ = make_stream(
        rng, lanes=lanes, hq=hkv * group, d=d, ps=ps, p=p, n=n)

    ref = paged_attention_varlen_reference(q, kp, vp, token_tbl, q_pos,
                                           exp_mode="lut")
    ker = paged_attention_varlen(q, kp, vp, token_tbl, q_pos,
                                 exp_mode="lut", block_pages=block_pages,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_varlen_int8_pool_close_to_float(rng):
    """INT8 pools (per-row scales, dequantised per page block) track the
    float varlen path within quantisation error, reference and kernel."""
    lanes, hq, hkv, d, ps, p = 3, 4, 2, 32, 8, 4
    n = p * lanes + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, lane_tbl, lens, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)

    def quant(pool):
        qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
        return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)

    kq, ks = quant(kp)
    vq, vs = quant(vp)
    want = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos))
    for impl in (paged_attention_varlen_reference,
                 lambda *a, **kw: paged_attention_varlen(*a, **kw,
                                                         interpret=True)):
        got = np.asarray(impl(q, kq, vq, token_tbl, q_pos,
                              k_scale=ks, v_scale=vs))
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 0.02, rel


def test_varlen_window_and_softcap(rng):
    """Sliding-window + logit-softcap masking agree with the naive oracle
    per token — local-attention layers ride the same packed stream."""
    lanes, hq, hkv, d, ps, p = 2, 4, 2, 16, 8, 4
    n = p * lanes
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, lane_tbl, lens, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)
    kw = dict(window=7, cap=15.0)

    got = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, **kw))
    want = contiguous_oracle("naive", q, cu, lane_tbl, lens, kp, vp, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_varlen_positions_helper():
    """varlen_positions: each lane segment ends at its live length − 1 —
    the packed restatement of the padded per-row bound kv_len − Lq + i."""
    cu = np.array([0, 3, 4, 8], np.int32)
    lens = np.array([10, 1, 6], np.int32)
    pos = varlen_positions(cu, lens)
    np.testing.assert_array_equal(pos, [7, 8, 9, 0, 2, 3, 4, 5])


def test_dead_rows_are_isolated(rng):
    """Bucket-padding rows (all-scratch table, q_pos 0) change nothing for
    live tokens and emit finite garbage themselves."""
    lanes, hq, hkv, d, ps, p = 2, 4, 2, 16, 8, 3
    n = p * lanes + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, _, _, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)
    t = q.shape[0]
    live = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos))

    pad = 3
    scratch = n - 1
    q2 = jnp.concatenate([q, jnp.asarray(
        rng.normal(size=(pad, hq, d)).astype(np.float32))])
    tbl2 = jnp.concatenate([token_tbl, jnp.full((pad, token_tbl.shape[1]),
                                                scratch, jnp.int32)])
    pos2 = jnp.concatenate([q_pos, jnp.zeros((pad,), jnp.int32)])
    both = np.asarray(paged_attention_varlen_reference(
        q2, kp, vp, tbl2, pos2))
    np.testing.assert_allclose(both[:t], live, atol=0, rtol=0)
    assert np.isfinite(both[t:]).all()


# ---------------------------------------------------------- q-block tiling --

def _decode_and_straddle_stream(rng, *, hq, hkv, d, ps, p, n):
    """A stream built to exercise the tiling edge cases: single-token decode
    lanes between prefill chunks, and chunk lengths chosen so lanes straddle
    q-block boundaries for every Bq in the test matrix."""
    nq = np.array([1, 5, 1, 7, 3])                        # decode + straddle
    lanes = len(nq)
    lens = np.array([int(rng.integers(nq[i], p * ps + 1))
                     for i in range(lanes)])
    cu = np.concatenate([[0], np.cumsum(nq)]).astype(np.int32)
    t = int(cu[-1])
    lane_tbl = np.stack([rng.permutation(n)[:p] for _ in range(lanes)])
    q = jnp.asarray(rng.normal(size=(t, hq, d)).astype(np.float32))
    q_pos = jnp.asarray(varlen_positions(cu, lens))
    token_tbl = jnp.asarray(lane_tbl[np.repeat(np.arange(lanes), nq)],
                            jnp.int32)
    return q, token_tbl, q_pos, cu


@pytest.mark.parametrize("block_q", [2, 3, 4, 8, 64])
@pytest.mark.parametrize("quant", [False, True])
def test_tiled_matches_untiled(rng, block_q, quant):
    """The q-block-tiled dataflow is a pure layout change: for every Bq
    (straddling lanes, single-token decode lanes, Bq > T) and both pool
    dtypes it reproduces the batch = T reference bit-for-bit-close —
    window + softcap riding along."""
    hq, hkv, d, ps, p = 4, 2, 16, 8, 3
    n = 16
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu = _decode_and_straddle_stream(
        rng, hq=hq, hkv=hkv, d=d, ps=ps, p=p, n=n)
    kw = dict(window=5, cap=20.0)
    if quant:
        def q8(pool):
            qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
            return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)
        kp, ks = q8(kp)
        vp, vs = q8(vp)
        kw.update(k_scale=ks, v_scale=vs)

    want = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, **kw))
    got = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, cu_seqlens=cu, block_q=block_q, **kw))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4),              # GQA group size
       st.integers(1, 4),              # lanes
       st.sampled_from([2, 3, 8]),     # Bq
       st.integers(0, 10_000))
def test_tiled_matches_contiguous_oracle(group, lanes, block_q, seed):
    """Tiled varlen == the contiguous per-lane oracle on random ragged
    streams (shuffled tables, ragged chunk and live lengths, every GQA
    packing) — the same bar the untiled path passes."""
    rng = np.random.default_rng(seed)
    hkv, d, ps, p = 2, 16, 4, 3
    hq = hkv * group
    n = p * lanes + 1
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, lane_tbl, lens, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)

    got = np.asarray(paged_attention_varlen_reference(
        q, kp, vp, token_tbl, q_pos, cu_seqlens=cu, block_q=block_q,
        exp_mode="lut"))
    want = contiguous_oracle("jnp", q, cu, lane_tbl, lens, kp, vp)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("block_pages", [1, 2, 8])
def test_tiled_kernel_interpret_matches_reference(rng, block_pages):
    """The Pallas kernel under q-block tiling (grid (q_block, kv_head,
    page_block), interpret mode) == the untiled jnp reference."""
    hq, hkv, d, ps, p = 4, 2, 16, 8, 3
    n = 16
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu = _decode_and_straddle_stream(
        rng, hq=hq, hkv=hkv, d=d, ps=ps, p=p, n=n)

    ref = paged_attention_varlen_reference(q, kp, vp, token_tbl, q_pos)
    ker = paged_attention_varlen(q, kp, vp, token_tbl, q_pos,
                                 cu_seqlens=cu, block_q=8,
                                 block_pages=block_pages, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_tiled_dequant_page_matches_block(rng):
    """`dequant="page"` is the same numbers as `dequant="block"` — the knob
    changes the multiply granularity, never a value."""
    hq, hkv, d, ps, p = 4, 2, 16, 4, 4
    n = 16
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu = _decode_and_straddle_stream(
        rng, hq=hq, hkv=hkv, d=d, ps=ps, p=p, n=n)

    def q8(pool):
        qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
        return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)
    kq, ks = q8(kp)
    vq, vs = q8(vp)
    outs = [np.asarray(paged_attention_varlen_reference(
        q, kq, vq, token_tbl, q_pos, k_scale=ks, v_scale=vs,
        cu_seqlens=cu, block_q=4, block_pages=2, dequant=dq))
        for dq in ("block", "page")]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6, rtol=1e-6)
    with pytest.raises(ValueError, match="dequant"):
        paged_attention_varlen_reference(
            q, kq, vq, token_tbl, q_pos, k_scale=ks, v_scale=vs,
            dequant="nope")


def test_q_block_layout_roundtrip():
    """Layout invariants: every live block holds contiguous same-lane rows,
    kv_len puts kernel row i at the token's own position, and `slot` is the
    exact inverse map (gather(blocks)[slot] == identity on live tokens)."""
    cu = np.array([0, 1, 6, 7, 14, 17], np.int32)         # nq = 1,5,1,7,3
    lens = np.array([9, 5, 31, 12, 3])
    t, bq = int(cu[-1]), 4
    q_pos = jnp.asarray(varlen_positions(cu, lens))
    rows, start, kv_len, slot = map(np.asarray,
                                    q_block_layout(jnp.asarray(cu), q_pos,
                                                   t, bq))
    s = len(cu) - 1
    assert rows.shape == (t // bq + s, bq)
    live_blocks = int(sum(-(-int(n) // bq) for n in np.diff(cu)))
    # per-lane: blocks tile the segment in order, bq rows at a time
    b = 0
    for i in range(s):
        n = int(cu[i + 1] - cu[i])
        for j in range(-(-n // bq)):
            assert start[b] == cu[i] + j * bq
            want = np.clip(np.arange(start[b], start[b] + bq), 0, t - 1)
            np.testing.assert_array_equal(rows[b], want)
            assert kv_len[b] == int(q_pos[start[b]]) + bq
            b += 1
    assert b == live_blocks
    assert (kv_len[live_blocks:] == 1).all()              # dead blocks pinned
    # inverse map: scattering block-major data back is the identity
    flat = rows.reshape(-1)
    np.testing.assert_array_equal(flat[slot], np.arange(t))


def test_validate_cu_seqlens_raises():
    with pytest.raises(ValueError, match="start at 0"):
        validate_cu_seqlens(np.array([1, 4], np.int32), 4)
    with pytest.raises(ValueError, match="non-decreasing"):
        validate_cu_seqlens(np.array([0, 5, 3, 8], np.int32), 8)
    with pytest.raises(ValueError, match="pseudo-segment"):
        validate_cu_seqlens(np.array([0, 3, 6], np.int32), 8)
    with pytest.raises(ValueError, match="1-D"):
        validate_cu_seqlens(np.array([0], np.int32), 0)
    validate_cu_seqlens(np.array([0, 3, 8], np.int32), 8)  # ok
    # traced boundaries skip value checks (serving validates on the host
    # copy at pack time) but still trace through
    out = jax.jit(lambda c: validate_cu_seqlens(c, 8))(
        jnp.asarray([0, 3, 8], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), [0, 3, 8])


def _pool_gather_rows(jaxpr, pool_shape):
    """Total rows gathered from pool-shaped operands anywhere in the graph
    (scan bodies included) — the structural KV-traffic count."""
    return sum(int(np.prod(eqn.invars[1].aval.shape[:-1]))
               for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == "gather"
               and tuple(eqn.invars[0].aval.shape) == pool_shape)


def test_tiled_page_gathers_scale_with_block_count(rng):
    """Structure, not timing: the traced tiled graph gathers KV pages
    O(T/Bq) times per page-block scan step where the untiled graph gathers
    O(T) — exactly proportional to the q-block count NB = T//Bq + S."""
    hq, hkv, d, ps, p = 4, 2, 16, 8, 3
    n, bq = 16, 8
    kp, vp = make_pool(rng, n, hkv, ps, d)
    nq = np.array([1, 13, 10])                            # T = 24
    lanes = len(nq)
    cu = np.concatenate([[0], np.cumsum(nq)]).astype(np.int32)
    t = int(cu[-1])
    lane_tbl = np.stack([rng.permutation(n)[:p] for _ in range(lanes)])
    token_tbl = jnp.asarray(lane_tbl[np.repeat(np.arange(lanes), nq)],
                            jnp.int32)
    q_pos = jnp.asarray(varlen_positions(
        cu, np.array([20, 13, 15])))
    q = jnp.asarray(rng.normal(size=(t, hq, d)).astype(np.float32))

    pool_shape = tuple(kp.shape)
    untiled = jax.make_jaxpr(lambda a: paged_attention_varlen_reference(
        a, kp, vp, token_tbl, q_pos))(q)
    tiled = jax.make_jaxpr(lambda a: paged_attention_varlen_reference(
        a, kp, vp, token_tbl, q_pos, cu_seqlens=cu, block_q=bq))(q)
    rows_u = _pool_gather_rows(untiled.jaxpr, pool_shape)
    rows_t = _pool_gather_rows(tiled.jaxpr, pool_shape)
    nb = t // bq + lanes                                  # 3 + 3
    assert rows_u > 0 and rows_t > 0
    assert rows_t < rows_u
    # exact proportionality: same scan skeleton, batch T vs batch NB
    assert rows_t * t == rows_u * nb, (rows_t, rows_u, t, nb)


# --------------------------------------------------------------- registry --

def _call(**kw):
    base = dict(lq=8, lkv=8, platform="cpu", static_lengths=False,
                has_kv_pos=False, inside_shard_map=False,
                has_page_table=True, is_ragged=True)
    base.update(kw)
    return AttentionCall(**base)


def test_resolution_ragged_calls_only_reach_paged_varlen():
    assert resolve_backend("auto", _call()).name == "paged_varlen"
    # the padded-paged backend and every contiguous backend refuse ragged
    for name in ("paged", "naive", "naive_decode", "jnp", "pallas"):
        with pytest.raises(ValueError, match="does not support"):
            resolve_backend(name, _call())
    # and the ragged backend refuses non-ragged calls
    for call in (_call(is_ragged=False),
                 _call(has_page_table=False, is_ragged=False)):
        with pytest.raises(ValueError, match="does not support"):
            resolve_backend("paged_varlen", call)
    # padded paged calls keep resolving to "paged", never the varlen path
    assert resolve_backend("auto", _call(is_ragged=False)).name == "paged"


def test_ragged_via_attention_api(rng):
    """attention(page_table=…, q_pos=…) resolves to paged_varlen and
    matches calling the varlen kernel module directly."""
    lanes, hq, hkv, d, ps, p = 2, 4, 2, 16, 8, 3
    n = 8
    kp, vp = make_pool(rng, n, hkv, ps, d)
    q, token_tbl, q_pos, cu, _, _, _ = make_stream(
        rng, lanes=lanes, hq=hq, d=d, ps=ps, p=p, n=n)

    packed = jnp.moveaxis(q, 0, 1)[None]               # (1, Hq, T, D)
    via_api = attention(packed, kp, vp, backend="auto", causal=True,
                        page_table=token_tbl, q_pos=q_pos)
    direct = paged_attention_varlen(q, kp, vp, token_tbl, q_pos)
    np.testing.assert_allclose(
        np.asarray(via_api[0]), np.asarray(jnp.moveaxis(direct, 0, 1)),
        atol=0, rtol=0)
