"""Paged serving (EngineCore + the deprecated PagedServingEngine shim):
equivalence with the contiguous engine, page lifecycle (free list, reuse
after release, pool-capped traffic), structured unsupported-layout
rejection, and the in-place decode guarantee (no gathered cache view in
the step graph)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import (EngineCore, PagedServingEngine, Request,
                           ServingEngine, UnsupportedCacheLayout)
from tests._jaxpr import jaxpr_shapes

warnings.filterwarnings("ignore", category=DeprecationWarning,
                        module="repro.serving.engine")


def build(name="deepseek-7b-smoke", **replace):
    cfg = get_config(name)
    if replace:
        cfg = cfg.replace(**replace)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def mixed_requests(cfg, rng, lens=(3, 9, 5, 7, 2)):
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 4 + (i % 3) * 3
                                        ).astype(np.int32),
                    max_new=n)
            for i, n in enumerate(lens)]


def by_uid(done):
    return {r.uid: r.tokens for r in done}


# ------------------------------------------------------------ equivalence --

def test_paged_matches_contiguous_greedy():
    """Greedy decode through the paged path (chunked prefill + in-place
    decode) must be token-identical to the slot-contiguous engine — paging
    and chunking are a memory layout, not a model change.  Also proves the
    deprecated PagedServingEngine shim still answers like an engine."""
    cfg, params = build()
    out = {}
    for make in [
        lambda: ServingEngine(cfg, params, slots=2, max_len=64),
        lambda: PagedServingEngine(cfg, params, slots=2, page_size=8,
                                   num_pages=16),
    ]:
        eng = make()
        for r in mixed_requests(cfg, np.random.default_rng(7)):
            eng.submit(r)
        out[type(eng).__name__] = by_uid(eng.run())
    assert out["PagedServingEngine"] == out["ServingEngine"]


def test_paged_matches_contiguous_quantized_cache():
    """INT8 KV caches page too (values + per-row scales share page tables),
    chunked prefill included."""
    cfg, params = build(kv_quant=True)
    outs = []
    for make in [
        lambda: ServingEngine(cfg, params, slots=2, max_len=64),
        lambda: PagedServingEngine(cfg, params, slots=2, page_size=8,
                                   num_pages=16),
    ]:
        eng = make()
        for r in mixed_requests(cfg, np.random.default_rng(3), lens=(4, 6, 3)):
            eng.submit(r)
        outs.append(by_uid(eng.run()))
    assert outs[0] == outs[1]


def test_prompt_crossing_page_boundaries():
    """Prompts longer than one page (and one chunk) prefill into multiple
    pages correctly — the chunk stream writes pages in place as it goes."""
    cfg, params = build()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 21).astype(np.int32)  # 3 pages

    eng = ServingEngine(cfg, params, slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt.copy(), max_new=6))
    want = eng.run()[0].tokens

    core = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=8,
                      chunk_size=8)
    core.submit(Request(uid=0, prompt=prompt.copy(), max_new=6))
    assert core.run()[0].tokens == want


# ---------------------------------------------------------- page lifecycle --

def test_pages_released_and_reused():
    """All pages return to the free list after a wave drains, and a second
    wave reusing those physical pages decodes identically."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=12,
                     chunk_size=8)

    def wave():
        for r in mixed_requests(cfg, np.random.default_rng(7)):
            eng.submit(r)
        done = by_uid(eng.run())
        eng.finished.clear()
        return done

    first = wave()
    assert eng.pages_in_use == 0
    assert sorted(eng.kv.free) == list(range(12))
    second = wave()                     # same traffic over recycled pages
    assert second == first
    assert eng.pages_in_use == 0


def test_pool_capped_traffic_drains():
    """A pool too small for all requests at once still drains — admission
    blocks on the budget, growth preempts-by-eviction — and no physical
    page is ever double-booked."""
    cfg, params = build()
    # each request peaks at ceil((7+8)/8) = 2 pages; a pool of 4 can hold
    # two grown requests — the other three wait or get evicted and resume
    eng = EngineCore(cfg, params, lanes=4, page_size=8, num_pages=4,
                     chunk_size=8)
    for i in range(5):
        eng.submit(Request(uid=i, prompt=np.arange(7, dtype=np.int32) + i,
                           max_new=8))
    while eng.scheduler.has_work():
        eng.step()
        live_pages = [p for t in eng.page_tables for p in t]
        assert len(live_pages) == len(set(live_pages)), "page double-booked"
        assert eng.pages_in_use <= 4
    assert len(eng.finished) == 5
    assert all(len(r.tokens) == 8 for r in eng.finished)
    assert eng.pages_in_use == 0


def test_lazy_page_growth():
    """Pages are allocated only as the token stream crosses page
    boundaries — a 6-token prompt starts on one page; the second page
    appears only once decode reaches row 8."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=8,
                     chunk_size=8)
    eng.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                       max_new=12))
    eng.step()
    assert len(eng.page_tables[0]) == 1          # 6-token prompt: one page
    for _ in range(4):
        eng.step()
    assert len(eng.page_tables[0]) == 2          # crossed row 8
    eng.run()
    assert eng.pages_in_use == 0


# ------------------------------------------------------- in-place serving --

def _step_jaxpr(eng, *, width, c, kv_len, q_len, npages):
    """Trace the engine's unified step at a given (chunk, table-width)."""
    tbl = np.full((eng.lanes, width), eng.kv.scratch, np.int32)
    tbl[0, :npages] = np.arange(npages, dtype=np.int32)
    return jax.make_jaxpr(eng._step)(
        eng.params, eng.kv.pool, jnp.asarray(tbl),
        jnp.zeros((eng.lanes, c), jnp.int32),
        jnp.asarray(kv_len, jnp.int32), jnp.asarray(q_len, jnp.int32))


@pytest.mark.parametrize("kv_quant", [False, True])
def test_decode_graph_has_no_gathered_view(kv_quant):
    """The paged decode step must never materialise the contiguous
    (B, …, width·page_size, …) cache view: every intermediate in the traced
    step graph is checked for the gathered-length dimension.  page_size=12
    with a 16-slot table makes that length 192 — longer than one attend
    block and a value no model/config dimension of the smoke config shares,
    so a hit can only be the gathered copy."""
    cfg, params = build(kv_quant=kv_quant)
    ps, width = 12, 16
    eng = EngineCore(cfg, params, lanes=2, page_size=ps, num_pages=32,
                     chunk_size=24)
    gathered_len = width * ps                              # 192

    jaxpr = _step_jaxpr(eng, width=width, c=1, kv_len=[151, 0],
                        q_len=[1, 0], npages=13)
    bad = [s for s in jaxpr_shapes(jaxpr.jaxpr) if gathered_len in s]
    assert not bad, f"gathered cache view in decode graph: {bad}"

    # sanity: the detector does catch the legacy gather copy
    tbl = np.full((2, width), eng.kv.scratch, np.int32)
    legacy = jax.make_jaxpr(
        lambda pool: eng.kv.gather(pool, jnp.asarray(tbl)))(eng.kv.pool)
    assert any(gathered_len in s for s in jaxpr_shapes(legacy.jaxpr))


@pytest.mark.parametrize("kv_quant", [False, True])
def test_chunked_prefill_graph_has_no_contiguous_cache(kv_quant):
    """Chunked prefill is in-place too: the traced chunk step contains no
    contiguous (B, n·page_size, …) KV intermediate — neither the padded
    table view (16·12 = 192) nor the old contiguous-prefill buffer that
    ``write_prefill`` used to scatter (13 pages · 12 = 156 rows for this
    prompt).  The contiguous-then-scatter path is structurally gone."""
    cfg, params = build(kv_quant=kv_quant)
    ps, width, chunk = 12, 16, 24
    eng = EngineCore(cfg, params, lanes=2, page_size=ps, num_pages=32,
                     chunk_size=chunk)
    # mid-prefill of a 150-token prompt: 120 rows resident, chunk 24 live
    jaxpr = _step_jaxpr(eng, width=width, c=chunk, kv_len=[120, 0],
                        q_len=[chunk, 0], npages=10)
    contiguous = {width * ps, 13 * ps, 150}
    bad = [s for s in jaxpr_shapes(jaxpr.jaxpr)
           if contiguous.intersection(s)]
    assert not bad, f"contiguous KV intermediate in chunk graph: {bad}"
    # and write_prefill itself is gone from the pool API
    from repro.serving.paged import PagedKVCache
    assert not hasattr(PagedKVCache, "write_prefill")


# ------------------------------------------------------------- rejection --

@pytest.mark.parametrize("name,page_size,layout", [
    ("gemma2-9b-smoke", 16, "ring_buffer_sliding_window"),
    ("falcon-mamba-7b-smoke", 16, "ssm_state"),
])
def test_unpageable_layouts_rejected(name, page_size, layout):
    """Unpageable cache layouts raise a structured UnsupportedCacheLayout
    naming the offending layout (not a silent/shape-soup ValueError).
    gemma2 is only unpageable when page_size > window (a ring buffer would
    appear inside one page) — at page_size ≤ window its local layers keep
    full per-page caches and serve fine (see test_engine_core)."""
    cfg, params = build(name)
    with pytest.raises(UnsupportedCacheLayout, match="paged KV cache"
                       ) as ei:
        EngineCore(cfg, params, lanes=2, page_size=page_size, num_pages=8)
    assert ei.value.layout == layout
    assert layout in str(ei.value)
    # still a ValueError, so pre-redesign handlers keep working
    assert isinstance(ei.value, ValueError)
