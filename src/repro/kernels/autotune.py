"""Roofline-guided autotuner for the tiled varlen paged-attention kernel.

The paper's throughput comes from co-designing kernel dataflow with the
memory hierarchy; CHARM-style CDSE does the software half by *enumerating*
tile candidates against an analytic resource model instead of hand-picking
them.  This module is that sweep for ``paged_attention_varlen``'s block
shapes:

    candidate  = (block_q, block_pages, dequant granularity)
    score      = perfmodel roofline (bytes-moved / FLOPs / grid steps)
               over a representative mix of serving steps
    validate   = optionally time the real kernel (jnp scan or interpret
                 mode on CPU CI, the compiled Pallas lowering on TPU)
    persist    = JSON table keyed ``{model}::{platform}`` that
                 ``core/attention_api.py`` consults at backend resolution

``KernelConfig`` is the unit of currency: frozen, hashable, safe to close
over as a static value in a jitted serving step.  ``source`` records
provenance ("default" | "tuned") so benchmark regressions are attributable
to the config that produced them.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.perfmodel.model import (PlatformSpec, platform_spec,
                                   varlen_attention_roofline,
                                   varlen_attention_traffic)

#: segments of one serving step: ``(n_new_tokens, kv_len_after)`` per lane
Workload = Sequence[Tuple[int, int]]


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Block shapes of the varlen paged-attention kernel (static facts)."""
    block_q: int = 8            # q-block rows; 1 = untiled batch=T dataflow
    # pages per jnp-scan step and per TPU-kernel grid step (clamped to the
    # table width); None = 128 rows' worth
    block_pages: Optional[int] = None
    dequant: str = "block"      # int8 scale granularity: "block" | "page"
    source: str = "default"     # "default" | "tuned" — provenance

    def describe(self) -> Dict[str, object]:
        return {"block_q": self.block_q, "block_pages": self.block_pages,
                "dequant": self.dequant, "source": self.source}


DEFAULT_CONFIG = KernelConfig()


@dataclasses.dataclass(frozen=True)
class KernelGeom:
    """The model/pool facts the roofline needs about one deployment."""
    hq: int
    hkv: int
    head_dim: int
    page_size: int
    kv_bytes: int = 4           # 4 = f32 pool, 1 = int8 (+ scale planes)

    @property
    def scaled(self) -> bool:
        return self.kv_bytes == 1


def geom_for(cfg, *, page_size: int, quantized: bool = False) -> KernelGeom:
    """KernelGeom from a ModelConfig (``num_heads``/``num_kv_heads``/
    ``d_head``) plus the engine's pool facts."""
    return KernelGeom(hq=cfg.num_heads, hkv=cfg.num_kv_heads or cfg.num_heads,
                      head_dim=cfg.d_head, page_size=page_size,
                      kv_bytes=1 if quantized else 4)


# --------------------------------------------------------------------------
# candidate space + representative workloads
# --------------------------------------------------------------------------

def candidate_space(page_size: int, *, max_block_q: int = 32,
                    max_block_pages: int = 8) -> List[KernelConfig]:
    """Every (Bq, pages-per-step, dequant) the sweep considers.

    Bq = 1 (the untiled baseline) stays in the space on purpose: on an
    all-decode workload tiling buys nothing, and the sweep should be able
    to say so rather than assume tiling always wins.
    """
    bqs = [b for b in (1, 4, 8, 16, 32) if b <= max_block_q]
    bps = [p for p in (1, 2, 4, 8) if p <= max_block_pages]
    return [KernelConfig(block_q=bq, block_pages=bp, dequant=dq)
            for bq in bqs for bp in bps
            for dq in ("block", "page")]


def default_workloads(*, lanes: int = 8, chunk: int = 32,
                      decode_ctx: int = 256) -> Dict[str, Workload]:
    """The serving-step mix the score integrates over: the two full-width
    extremes the padded dispatch used to special-case, plus the mixed step
    ragged batching exists for."""
    return {
        "all_decode": [(1, decode_ctx)] * lanes,
        "all_prefill": [(chunk, chunk)] * lanes,
        "mixed": [(chunk, chunk), (chunk, 2 * chunk)]
                 + [(1, decode_ctx)] * (lanes - 2),
    }


# --------------------------------------------------------------------------
# scoring + optional measurement
# --------------------------------------------------------------------------

def predict_step_s(config: KernelConfig, geom: KernelGeom,
                   workloads: Dict[str, Workload],
                   spec: PlatformSpec) -> float:
    """Roofline-predicted seconds summed over the workload mix."""
    bp = config.block_pages or max(1, 128 // max(geom.page_size, 1))
    total = 0.0
    for segments in workloads.values():
        traffic = varlen_attention_traffic(
            segments, block_q=config.block_q, block_pages=bp,
            page_size=geom.page_size, hq=geom.hq, hkv=geom.hkv,
            head_dim=geom.head_dim, kv_bytes=geom.kv_bytes,
            scaled=geom.scaled)
        total += varlen_attention_roofline(
            spec, traffic, block_pages=bp, dequant=config.dequant)
    return total


def measure_step_s(config: KernelConfig, geom: KernelGeom,
                   workloads: Dict[str, Workload], *,
                   interpret: Optional[bool] = None,
                   iters: int = 3) -> float:
    """Time the real kernel on a synthetic pool built from the workloads.

    ``interpret=None`` is the platform default (jnp scan on CPU, compiled
    Pallas on TPU); ``interpret=True`` forces the Pallas kernel in
    interpret mode — the CPU-CI way to validate the kernel lowering itself.
    Returns the *minimum* over ``iters`` repetitions — the noise-robust
    microbenchmark estimator (scheduler hiccups only ever add time).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (paged_attention_varlen,
                                               varlen_positions)

    rng = np.random.default_rng(0)
    best_total = 0.0
    for segments in workloads.values():
        lens_new = [n for n, _ in segments]
        kv_lens = [kv for _, kv in segments]
        cu = np.concatenate([[0], np.cumsum(lens_new)]).astype(np.int32)
        t = int(cu[-1])
        ps = geom.page_size
        per_lane = max(-(-max(kv_lens) // ps), 1)
        n_pages = per_lane * len(segments)
        shape = (n_pages + 1, geom.hkv, ps, geom.head_dim)
        if geom.scaled:
            k_pool = jnp.asarray(
                rng.integers(-127, 127, size=shape).astype(np.int8))
            v_pool = jnp.asarray(
                rng.integers(-127, 127, size=shape).astype(np.int8))
            k_scale = jnp.asarray(
                rng.uniform(0.01, 0.03, size=shape[:3]).astype(np.float32))
            v_scale = k_scale
        else:
            k_pool = jnp.asarray(
                rng.normal(size=shape).astype(np.float32))
            v_pool = jnp.asarray(
                rng.normal(size=shape).astype(np.float32))
            k_scale = v_scale = None
        q = jnp.asarray(
            rng.normal(size=(t, geom.hq, geom.head_dim)).astype(np.float32))
        tbl = np.zeros((t, per_lane), np.int32)
        for i in range(len(segments)):
            tbl[cu[i]:cu[i + 1]] = np.arange(
                i * per_lane, (i + 1) * per_lane, dtype=np.int32)
        token_pages = jnp.asarray(tbl)
        q_pos = jnp.asarray(varlen_positions(cu, kv_lens))

        def run(q, cu_d):
            return paged_attention_varlen(
                q, k_pool, v_pool, token_pages, q_pos, cu_seqlens=cu_d,
                k_scale=k_scale, v_scale=v_scale,
                block_q=config.block_q, block_pages=config.block_pages,
                dequant=config.dequant, interpret=interpret)

        fn = jax.jit(run)
        cu_d = jnp.asarray(cu)
        fn(q, cu_d).block_until_ready()       # compile outside the clock
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(q, cu_d).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        best_total += best
    return best_total


def tune(geom: KernelGeom, *, platform: Optional[str] = None,
         workloads: Optional[Dict[str, Workload]] = None,
         candidates: Optional[List[KernelConfig]] = None,
         measure: bool = False, interpret: Optional[bool] = None,
         top_k_measure: int = 3) -> Tuple[KernelConfig, List[Dict]]:
    """Sweep the candidate space; return (winner, per-candidate report).

    Pure roofline by default; ``measure=True`` re-ranks the roofline's
    ``top_k_measure`` finalists by timing the real kernel — the cheap
    analytic model prunes, the hardware decides.

    The incumbent ``DEFAULT_CONFIG`` is always in the sweep, so the winner
    predicts no worse than the default *by construction* — CI asserts
    exactly that (measured times are too noisy at CI scale to gate on).
    """
    import jax
    plat = platform or jax.default_backend()
    spec = platform_spec(plat)
    wl = workloads or default_workloads()
    cands = list(candidates or candidate_space(geom.page_size))
    if DEFAULT_CONFIG not in cands:
        cands.append(DEFAULT_CONFIG)
    report = []
    for c in cands:
        report.append({"config": c.describe(),
                       "predicted_s": predict_step_s(c, geom, wl, spec)})
    order = sorted(range(len(cands)), key=lambda i: report[i]["predicted_s"])
    if measure:
        finalists = order[:max(1, top_k_measure)]
        for i in finalists:
            report[i]["measured_s"] = measure_step_s(
                cands[i], geom, wl, interpret=interpret)
        best = min(finalists, key=lambda i: report[i]["measured_s"])
    else:
        best = order[0]
    winner = dataclasses.replace(cands[best], source="tuned")
    return winner, report


# --------------------------------------------------------------------------
# persistence: the per-(model, platform) table
# --------------------------------------------------------------------------

def table_path(path: Optional[os.PathLike] = None) -> Path:
    """Resolution order: explicit arg → $REPRO_AUTOTUNE_PATH → the
    committed repo table next to the model configs."""
    if path is not None:
        return Path(path)
    env = os.environ.get("REPRO_AUTOTUNE_PATH")
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[1] / "configs" / "autotune.json"


def _key(model: str, platform: str) -> str:
    return f"{model}::{platform}"


def load_table(path: Optional[os.PathLike] = None) -> Dict[str, Dict]:
    p = table_path(path)
    if not p.exists():
        return {}
    try:
        return json.loads(p.read_text())
    except (OSError, json.JSONDecodeError):
        return {}


def save_config(model: str, platform: str, config: KernelConfig, *,
                path: Optional[os.PathLike] = None) -> Path:
    p = table_path(path)
    table = load_table(p)
    table[_key(model, platform)] = config.describe()
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n")
    return p


def resolve_config(model: str, platform: Optional[str] = None, *,
                   path: Optional[os.PathLike] = None) -> KernelConfig:
    """Tuned config for (model, platform) if persisted, else the default.

    Falls back ``model::platform`` → ``default::platform`` →
    ``DEFAULT_CONFIG`` so a table tuned for one model still seeds its
    platform's siblings.
    """
    if platform is None:
        import jax
        platform = jax.default_backend()
    table = load_table(path)
    for key in (_key(model, platform), _key("default", platform)):
        entry = table.get(key)
        if entry is not None:
            known = {f.name for f in dataclasses.fields(KernelConfig)}
            entry = {k: v for k, v in entry.items() if k in known}
            return KernelConfig(**{**entry, "source": "tuned"})
    return DEFAULT_CONFIG


# --------------------------------------------------------------------------
# process-wide active config (what `attention()` consults)
# --------------------------------------------------------------------------

_ACTIVE: Optional[KernelConfig] = None


def set_active_config(config: Optional[KernelConfig]) -> None:
    """Pin the config `attention()` uses for ragged calls that don't pass
    one explicitly (EngineCore pins its resolved config at init).  ``None``
    reverts to on-disk resolution."""
    global _ACTIVE
    _ACTIVE = config


def active_config() -> KernelConfig:
    if _ACTIVE is not None:
        return _ACTIVE
    return resolve_config("default")
