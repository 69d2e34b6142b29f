"""The page-block paged-attention kernel at its edges: one grid step walks
``block_pages`` pages of 16 rows through a double-buffered manual DMA, so
the cases are the ones the block boundaries make — table widths the block
does not divide, tables narrower than a block, lengths that end exactly on
a block boundary, lanes pinned to length 1 — crossed with G 1 and 12,
decode and 32-row chunks, float and int8 pools, and window + softcap.
Interpret mode against the jnp page-block reference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.streaming_attention import quantize_kv_rows
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_reference)

PS = 16

# (group, Lq, table width P, kv_len per lane).  Lane lengths hit an exact
# multiple of bp·16 (bp 2: 32, 64; bp 8: 128), a length of 1 (the pin of a
# dead q-block), a partial last page and a full table.  P = 5 and 3 leave
# the last page block of bp 2 short, P = 10 that of bp 8, and P = 3 and
# 5 < 8 clamp bp to the table.
CASES = {
    "decode-g1": (1, 1, 5, [32, 1, 80, 64, 17]),
    "chunk32-g1": (1, 32, 10, [128, 64, 160, 1]),
    "chunk32-g12": (12, 32, 3, [48, 33, 32]),
}
VARIANTS = {
    "f32": ("f32", {}),
    "int8": ("int8", {}),
    "window-softcap": ("f32", dict(window=40, cap=15.0)),
}


def _q8(pool):
    n, hkv, ps, d = pool.shape
    qv, s = quantize_kv_rows(pool.reshape(1, n * hkv, ps, d))
    return qv.reshape(n, hkv, ps, d), s.reshape(n, hkv, ps)


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("block_pages", [1, 2, 8])
def test_page_block_kernel_matches_reference(block_pages, case, variant):
    group, lq, p, lens = CASES[case]
    kv, masks = VARIANTS[variant]
    rng = np.random.default_rng(block_pages * 31 + len(case) + len(variant))
    b, hkv, d = len(lens), 1, 8
    n = p * b + 1
    kp, vp = (jnp.asarray(rng.normal(size=(n, hkv, PS, d))
                          .astype(np.float32)) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, hkv * group, lq, d))
                    .astype(np.float32))
    tbl = jnp.asarray(np.stack([rng.permutation(n)[:p] for _ in range(b)]),
                      jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kw = dict(masks, exp_mode="lut")
    if kv == "int8":
        kp, ks = _q8(kp)
        vp, vs = _q8(vp)
        kw.update(k_scale=ks, v_scale=vs)
    ref = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens, **kw))
    ker = np.asarray(paged_attention(q, kp, vp, tbl, lens,
                                     block_pages=block_pages,
                                     interpret=True, **kw))
    # Chunk rows at negative positions (a chunk longer than its lane's
    # length, as in a pinned dead q-block) are never read; compare the
    # rows that exist.
    pos = np.asarray(lens)[:, None] - lq + np.arange(lq)[None, :]
    live = np.broadcast_to((pos >= 0)[:, None, :, None], ref.shape)
    assert np.isfinite(ker).all()
    np.testing.assert_allclose(ker[live], ref[live], atol=2e-5, rtol=1e-4)
