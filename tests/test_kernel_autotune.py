"""Roofline-guided kernel autotuner: candidate sweep, traffic model,
persistence, and the config-threading contract — a tuned `KernelConfig`
must reach the varlen kernel from every entry point (explicit argument,
process-wide active config, EngineCore resolution at init) and be recorded
where benchmarks can see it (StepOutput debug stats)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.autotune import (DEFAULT_CONFIG, KernelConfig, KernelGeom,
                                    active_config, candidate_space,
                                    default_workloads, geom_for,
                                    predict_step_s, resolve_config,
                                    save_config, set_active_config,
                                    table_path, tune)
from repro.perfmodel.model import (platform_spec, varlen_attention_roofline,
                                   varlen_attention_traffic)


@pytest.fixture(autouse=True)
def _isolate_active_config():
    """Never leak a pinned process-wide config between tests."""
    set_active_config(None)
    yield
    set_active_config(None)


# ----------------------------------------------------------- candidates ----

def test_candidate_space_contents():
    cands = candidate_space(page_size=8)
    assert len(cands) == len(set(cands))            # frozen → hashable, dedup
    assert KernelConfig(block_q=1, block_pages=1, dequant="block") in cands
    assert any(c.block_q == 1 for c in cands)       # untiled baseline kept
    assert {c.dequant for c in cands} == {"block", "page"}
    assert all(c.source == "default" for c in cands)
    small = candidate_space(page_size=8, max_block_q=8, max_block_pages=2)
    assert max(c.block_q for c in small) <= 8
    assert max(c.block_pages for c in small) <= 2


def test_geom_for_reads_model_config():
    from repro.configs import get_config
    cfg = get_config("deepseek-7b-smoke")
    g = geom_for(cfg, page_size=8, quantized=True)
    assert (g.hq, g.page_size, g.kv_bytes) == (cfg.num_heads, 8, 1)
    assert g.scaled


# -------------------------------------------------------- traffic model ----

def test_traffic_kv_bytes_fall_with_block_q():
    """The tentpole claim in analytic form: each KV page is read once per
    q-block, so bytes_kv on a prefill chunk falls ~Bq× as Bq grows (until
    one block covers the chunk)."""
    segments = [(32, 64)] * 4
    kw = dict(block_pages=2, page_size=8, hq=8, hkv=2, head_dim=64)
    byq = {bq: varlen_attention_traffic(segments, block_q=bq, **kw)
           for bq in (1, 4, 8, 16, 32)}
    kv = [byq[bq]["bytes_kv"] for bq in (1, 4, 8, 16, 32)]
    assert all(a > b for a, b in zip(kv, kv[1:])), kv
    assert byq[1]["bytes_kv"] > 3 * byq[8]["bytes_kv"]
    pages = [byq[bq]["pages_read"] for bq in (1, 4, 8, 16, 32)]
    assert all(a >= b for a, b in zip(pages, pages[1:])), pages


def test_traffic_decode_indifferent_to_block_q():
    """All-decode (1 new token per lane): tiling buys nothing — the sweep
    must be able to conclude Bq=1 is fine there."""
    segments = [(1, 256)] * 8
    kw = dict(block_pages=4, page_size=16, hq=8, hkv=2, head_dim=64)
    t1 = varlen_attention_traffic(segments, block_q=1, **kw)
    t8 = varlen_attention_traffic(segments, block_q=8, **kw)
    assert t1["bytes_kv"] == t8["bytes_kv"]


def test_traffic_grid_steps_fall_with_block_pages():
    segments = [(16, 128)] * 4
    kw = dict(block_q=8, page_size=8, hq=4, hkv=2, head_dim=32)
    steps = [varlen_attention_traffic(segments, block_pages=bp,
                                      **kw)["grid_steps"]
             for bp in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(steps, steps[1:])), steps


def test_unknown_platform_is_an_error():
    """No silent fallback to the cpu row for a platform the model lacks."""
    with pytest.raises(KeyError, match="gpu"):
        platform_spec("gpu")


def test_roofline_terms():
    """max(mem, compute) + dispatch, plus the per-page dequant penalty only
    when dequant='page' actually splits the multiply."""
    spec = platform_spec("cpu")
    segments = [(16, 64)] * 2
    traffic = varlen_attention_traffic(
        segments, block_q=8, block_pages=4, page_size=8, hq=4, hkv=2,
        head_dim=32)
    base = varlen_attention_roofline(spec, traffic, block_pages=4)
    assert base > 0
    floor = max(traffic["bytes_total"] / (spec.mem_bw_gbs * 1e9),
                traffic["flops"] / spec.flops)
    assert base >= floor
    paged = varlen_attention_roofline(spec, traffic, block_pages=4,
                                      dequant="page")
    assert paged >= base
    single = varlen_attention_roofline(spec, traffic, block_pages=1,
                                       dequant="page")
    assert single == varlen_attention_roofline(spec, traffic, block_pages=1)


def test_predict_finite_over_whole_space():
    geom = KernelGeom(hq=4, hkv=2, head_dim=32, page_size=8)
    wl = default_workloads(lanes=4, chunk=16, decode_ctx=64)
    spec = platform_spec("cpu")
    for c in candidate_space(page_size=8):
        s = predict_step_s(c, geom, wl, spec)
        assert np.isfinite(s) and s > 0, c


# ---------------------------------------------------------------- tune -----

def test_tune_picks_tiled_for_prefill_and_reports_all():
    geom = KernelGeom(hq=4, hkv=2, head_dim=32, page_size=8)
    wl = {"prefill": [(32, 32)] * 4}
    winner, report = tune(geom, platform="cpu", workloads=wl)
    assert winner.source == "tuned"
    # the whole space plus the incumbent default
    assert len(report) == len(candidate_space(page_size=8)) + 1
    # tuned ≤ default under the tuner's own metric, by construction
    pred_default = next(r["predicted_s"] for r in report
                        if r["config"]["source"] == "default"
                        and r["config"]["block_pages"] is None)
    assert min(r["predicted_s"] for r in report) <= pred_default
    assert winner.block_q > 1        # prefill chunks reward tiling
    best_pred = min(r["predicted_s"] for r in report)
    assert any(r["config"]["block_q"] == winner.block_q
               and r["predicted_s"] == best_pred for r in report)


def test_tune_measure_rescores_finalists():
    geom = KernelGeom(hq=2, hkv=1, head_dim=16, page_size=4)
    wl = {"mixed": [(4, 8), (1, 8)]}
    winner, report = tune(geom, platform="cpu", workloads=wl, measure=True,
                          top_k_measure=2)
    timed = [r for r in report if "measured_s" in r]
    assert len(timed) == 2
    assert all(r["measured_s"] > 0 for r in timed)
    assert winner.source == "tuned"
    assert winner.describe()["block_q"] in {t["config"]["block_q"]
                                            for t in timed}


# --------------------------------------------------------- persistence -----

def test_save_resolve_roundtrip(tmp_path):
    path = tmp_path / "autotune.json"
    tuned = KernelConfig(block_q=16, block_pages=4, dequant="page",
                         source="tuned")
    save_config("smoke", "cpu", tuned, path=path)
    got = resolve_config("smoke", "cpu", path=path)
    assert (got.block_q, got.block_pages, got.dequant) == (16, 4, "page")
    assert got.source == "tuned"
    # platform fallback: an unknown model inherits default::cpu, not smoke's
    save_config("default", "cpu", KernelConfig(block_q=4, source="tuned"),
                path=path)
    assert resolve_config("other-model", "cpu", path=path).block_q == 4
    # no entry at all → the hardcoded default
    assert resolve_config("other-model", "tpu", path=path) == DEFAULT_CONFIG
    # the table is plain JSON, one entry per (model, platform)
    table = json.loads(path.read_text())
    assert set(table) == {"smoke::cpu", "default::cpu"}


def test_resolve_ignores_unknown_table_keys(tmp_path):
    """Forward compat: a table written by a newer repo (extra fields) must
    not crash resolution."""
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({"m::cpu": {
        "block_q": 8, "block_pages": 2, "dequant": "block",
        "source": "tuned", "tuned_at": "2026-08-09", "score": 1.5}}))
    got = resolve_config("m", "cpu", path=path)
    assert (got.block_q, got.block_pages) == (8, 2)


def test_env_var_points_at_table(tmp_path, monkeypatch):
    path = tmp_path / "env_table.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(path))
    assert table_path() == path
    save_config("m", "cpu", KernelConfig(block_q=32, source="tuned"))
    assert resolve_config("m", "cpu").block_q == 32
    monkeypatch.delenv("REPRO_AUTOTUNE_PATH")
    assert table_path().name == "autotune.json"
    assert table_path().parent.name == "configs"    # the committed table


def test_committed_repo_table_resolves():
    """The persisted per-(model, platform) table shipped in the repo parses
    and resolves for the smoke model on cpu."""
    p = table_path()
    assert p.exists(), "src/repro/configs/autotune.json missing"
    table = json.loads(p.read_text())
    assert table, "committed autotune table is empty"
    for key, entry in table.items():
        assert "::" in key
        assert entry["block_q"] >= 1
    got = resolve_config("deepseek-7b-smoke", "cpu")
    assert got.source in ("tuned", "default")


# ----------------------------------------------------- config threading ----

def _tiny_stream(rng, *, hq=4, hkv=2, d=16, ps=8, p=3, n=12):
    from repro.kernels.paged_attention import varlen_positions
    nq = np.array([1, 6, 3])
    lens = np.array([5, 6, 9])
    cu = np.concatenate([[0], np.cumsum(nq)]).astype(np.int32)
    t = int(cu[-1])
    lane_tbl = np.stack([rng.permutation(n)[:p] for _ in range(len(nq))])
    q = jnp.asarray(rng.normal(size=(t, hq, d)).astype(np.float32))
    tbl = jnp.asarray(lane_tbl[np.repeat(np.arange(len(nq)), nq)], jnp.int32)
    pos = jnp.asarray(varlen_positions(cu, lens))
    kp = jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(n, hkv, ps, d)).astype(np.float32))
    return q, kp, vp, tbl, pos, cu


def test_attention_api_threads_kernel_config(rng):
    """attention(kernel_config=…) reaches the kernel: the traced graph is
    the tiled one (fewer pool gathers), and the numbers match both the
    direct tiled call and the untiled reference."""
    from repro.core.attention_api import attention
    from repro.kernels.paged_attention import (
        paged_attention_varlen, paged_attention_varlen_reference)
    from tests.test_ragged_attention import _pool_gather_rows

    q, kp, vp, tbl, pos, cu = _tiny_stream(rng)
    packed = jnp.moveaxis(q, 0, 1)[None]
    cfg_tiled = KernelConfig(block_q=4)
    cfg_flat = KernelConfig(block_q=1)

    def call(kc):
        return attention(packed, kp, vp, backend="auto", causal=True,
                         page_table=tbl, q_pos=pos, cu_seqlens=cu,
                         kernel_config=kc)

    want = np.asarray(paged_attention_varlen_reference(q, kp, vp, tbl, pos))
    got = np.asarray(jnp.moveaxis(call(cfg_tiled)[0], 0, 1))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    direct = paged_attention_varlen(q, kp, vp, tbl, pos, cu_seqlens=cu,
                                    block_q=4)
    np.testing.assert_allclose(got, np.asarray(direct), atol=0, rtol=0)

    pool_shape = tuple(kp.shape)
    rows = {kc.block_q: _pool_gather_rows(
        jax.make_jaxpr(lambda a: call(kc))(packed).jaxpr, pool_shape)
        for kc in (cfg_tiled, cfg_flat)}
    assert 0 < rows[4] < rows[1], rows


def test_active_config_hook(rng, tmp_path, monkeypatch):
    """No explicit config → `attention()` uses the process-wide active
    config; unset → on-disk resolution (pointed at an empty table here, so
    the hardcoded default)."""
    from repro.core.attention_api import attention
    from tests.test_ragged_attention import _pool_gather_rows

    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(tmp_path / "none.json"))
    assert active_config() == DEFAULT_CONFIG
    pinned = KernelConfig(block_q=2, source="tuned")
    set_active_config(pinned)
    assert active_config() == pinned

    q, kp, vp, tbl, pos, cu = _tiny_stream(rng)
    packed = jnp.moveaxis(q, 0, 1)[None]
    pool_shape = tuple(kp.shape)

    def trace_rows():
        # a FRESH closure per trace: jax caches traces on function identity,
        # which is exactly why EngineCore pins its config at init instead of
        # reading the hook inside a jitted step
        fn = lambda a: attention(a, kp, vp, backend="auto", causal=True,
                                 page_table=tbl, q_pos=pos, cu_seqlens=cu)
        return _pool_gather_rows(jax.make_jaxpr(fn)(packed).jaxpr,
                                 pool_shape)

    rows_pinned = trace_rows()
    set_active_config(KernelConfig(block_q=1))
    rows_flat = trace_rows()
    assert 0 < rows_pinned < rows_flat, (rows_pinned, rows_flat)


def test_engine_resolves_and_reports_config(tmp_path, monkeypatch):
    """EngineCore pins its config at init (explicit beats on-disk) and
    every ragged StepOutput carries it in debug stats."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineCore, Request

    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    table = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(table))
    save_config(cfg.name, jax.default_backend(),
                KernelConfig(block_q=16, block_pages=2, source="tuned"))
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=16,
                     chunk_size=16, mode="ragged")
    assert (eng.kernel_config.block_q, eng.kernel_config.source) == (16,
                                                                     "tuned")

    override = KernelConfig(block_q=4, source="tuned")
    eng2 = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=16,
                      chunk_size=16, mode="ragged", kernel_config=override)
    assert eng2.kernel_config == override

    rng = np.random.default_rng(0)
    eng2.submit(Request(uid=0, prompt=rng.integers(
        0, cfg.vocab_size, 5).astype(np.int32), max_new=2))
    out = eng2.step()
    assert out.kernel_config == override.describe()
    assert out.kernel_config["source"] == "tuned"


def test_kernel_config_is_static_and_hashable():
    """The config closes over a jitted step as a static value — it must be
    frozen, hashable and equality-stable."""
    a = KernelConfig(block_q=8, block_pages=2)
    b = KernelConfig(block_q=8, block_pages=2)
    assert a == b and hash(a) == hash(b)
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.block_q = 4
