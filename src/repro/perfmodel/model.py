"""Analytical latency/energy model of HASTILY vs PUMA vs A40 (paper Figs 7-13).

Reproduces the paper's cycle-level-simulator evaluation as closed-form
structural formulas over the Table II hardware description.  Soft constants
the paper doesn't print are calibrated on the Fig. 7 anchors (see
``hardware.py``); everything else is *predicted* and checked against the
paper's claims in tests/test_perfmodel.py:

  Fig 7   softmax latency (PUMA / UCLM / UCLM+multicore) × l × ALU width
  Fig 8   softmax energy, PUMA ≈ 1.6× HASTILY for l > 1024
  Fig 9   encoder-layer latency (softmax accel ±, fine-grained pipelining ±)
  Fig 10  runtime share of softmax (PUMA 38% → 13% at l=1024)
  Fig 12  end-to-end TOPS (BERT-Base 158, BERT-Large 263; PUMA 26, GPU 19)
  Fig 13  TOPS/W (HASTILY ≈ 8 regardless of model/batch)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

from repro.perfmodel.hardware import DEFAULT_HW, GPU, Hardware


# --------------------------------------------------------------------------
# softmax (per vector of length l) — Fig 7 / Fig 8
# --------------------------------------------------------------------------

def softmax_cores(hw: Hardware, l: int) -> int:
    """Cores the multicore softmax spreads one l-vector over.

    K^T is mapped 64-column tiles per UCLM → 16·64 = 1024 columns per core's
    UCLMs live in 1 core, but the VFU work is spread over the (two-tile)
    neighbourhood: 1 core per 512 columns, ≤ 16 (paper §III-B2)."""
    return max(1, min(16, l // 512))


def softmax_latency_s(hw: Hardware, l: int, mode: str,
                      alu_width: int | None = None) -> float:
    """mode ∈ {puma, uclm, multicore, hastily}.

    ``hastily`` = min(uclm, multicore): the compiler schedules whichever is
    faster (multicore only pays off once the tree gather amortises —
    matching Fig 7's "no difference at smaller l")."""
    w = alu_width or hw.alu_width
    cyc = hw.cycle_s
    if mode == "puma":
        # max, sub, reduce on VFU + software exp + reciprocal-multiply
        per_elem = 3 + hw.c_exp_sw + hw.c_div
        return (l / w) * per_elem * cyc
    if mode == "uclm":
        lookup = math.ceil(l / hw.arrays_per_core) * hw.c_lookup
        per_elem = 3 + hw.c_vfu_misc + hw.c_div
        return ((l / w) * per_elem + lookup) * cyc
    if mode == "multicore":
        n = softmax_cores(hw, l)
        lc = l / n
        lookup = math.ceil(lc / hw.arrays_per_core) * hw.c_lookup
        per_elem = 3 + hw.c_vfu_misc + hw.c_div
        tree = 2 * math.log2(max(n, 2)) * hw.c_comm if n > 1 else 0.0
        return ((lc / w) * per_elem + lookup + tree) * cyc
    if mode == "hastily":
        return min(softmax_latency_s(hw, l, "uclm", w),
                   softmax_latency_s(hw, l, "multicore", w))
    raise ValueError(mode)


def softmax_energy_j(hw: Hardware, l: int, mode: str) -> float:
    """Per-vector softmax energy (Fig 8 trends).

    Common base: 5 VFU element ops + 2 RF word accesses; PUMA adds the
    software-exp surcharge (calibrated to the paper's ≈1.6× ratio); the LUT
    path adds the (small) SRAM-LT energy; multicore adds the tree-gather
    shared-memory words — small, matching Fig 8's "small difference between
    UCLM only and multi-core"."""
    base = 5 * hw.e_vfu_op + 2 * hw.e_rf_word
    if mode == "puma":
        return l * (base + hw.e_exp_sw_extra)
    e_lut = hw.p_uclm_lt * (hw.c_lookup * hw.cycle_s) / hw.array_cols
    e = l * (base + e_lut)
    if mode in ("multicore", "hastily"):
        n = softmax_cores(hw, l)
        if n > 1 and (mode == "multicore"
                      or softmax_latency_s(hw, l, "multicore")
                      < softmax_latency_s(hw, l, "uclm")):
            e += 2 * math.log2(n) * n * hw.e_comm_word
    return e


# --------------------------------------------------------------------------
# encoder layer — Fig 9 / 10 / 11
# --------------------------------------------------------------------------

def _layer_op_counts(l: int, d: int, d_ff: int | None = None,
                     heads: int | None = None) -> Dict[str, float]:
    """MAC·2 op counts per encoder layer (paper's TOPS convention)."""
    d_ff = d_ff or 4 * d
    heads = heads or d // 64
    static = l * (4 * d * d + 2 * d * d_ff)          # QKVO + FF1 + FF2
    dynamic = 2 * l * l * d                           # QK^T + SV
    return {"static": 2 * static, "dynamic": 2 * dynamic,
            "total": 2 * (static + dynamic)}


def mvm_stage_s(hw: Hardware) -> float:
    return hw.t_mvm_ns * 1e-9


def encoder_layer_latency_s(hw: Hardware, l: int, d: int, *,
                            softmax_mode: str = "hastily",
                            pipelined: str = "fine",
                            d_ff: int | None = None) -> float:
    """One encoder layer (attention + FFN), Fig 9 model.

    pipelined ∈ {"none", "coarse", "fine"}:
      none    — the six MatMul blocks run back-to-back, l vectors each,
                plus l softmax vectors (Fig 10's un-pipelined breakdown);
      coarse  — PUMA's block dataflow: MatMuls overlap (fill+drain ≈ 2·l
                stages) but softmax still serialises on the VFU;
      fine    — HASTILY §IV: everything overlaps; the softmax only shows
                when slower than one crossbar stage.
    """
    t_mvm = mvm_stage_s(hw)
    t_sm = softmax_latency_s(hw, l, softmax_mode)
    if pipelined == "none":
        return 6 * l * t_mvm + l * t_sm
    if pipelined == "coarse":
        return 2 * l * t_mvm + l * t_sm
    return 2 * l * max(t_mvm, t_sm)


def softmax_fraction(hw: Hardware, l: int, d: int, mode: str) -> float:
    """Fig 10: softmax share of un-pipelined layer runtime."""
    t_total = encoder_layer_latency_s(hw, l, d, softmax_mode=mode,
                                      pipelined="none")
    t_sm = l * softmax_latency_s(hw, l, mode)
    return t_sm / t_total


def encoder_layer_energy_j(hw: Hardware, l: int, d: int, *,
                           softmax_mode: str = "hastily",
                           d_ff: int | None = None) -> float:
    """Fig 11: dominated by crossbar MVM (ADC) energy — per-op count.

    The paper notes PUMA-vs-HASTILY layer energy is "negligible" apart —
    both are e_op · ops; only the softmax term differs."""
    ops = _layer_op_counts(l, d, d_ff)
    e_mvm = ops["total"] * hw.e_op
    e_sm = l * softmax_energy_j(hw, l, softmax_mode)
    return e_mvm + e_sm


# --------------------------------------------------------------------------
# end-to-end — Fig 12 / 13
# --------------------------------------------------------------------------

def bert_ops(n_layers: int, l: int, d: int, d_ff: int) -> float:
    per = _layer_op_counts(l, d, d_ff)["total"]
    return n_layers * per


def end_to_end_latency_s(hw: Hardware, n_layers: int, l: int, d: int,
                         d_ff: int, *, pipelined: str = "fine",
                         softmax_mode: str = "hastily",
                         batch: int = 1) -> float:
    """HASTILY pipeline: N layers drain in (N+1)·l MVM-stage times (§IV).

    Fine-grained pipelining holds ≤2 batches' weights resident (paper §VI-C);
    beyond that, batches serialise.  PUMA holds 4 batches (coarse mode)."""
    if pipelined == "fine":
        t_sm = softmax_latency_s(hw, l, softmax_mode)
        stage = max(mvm_stage_s(hw), t_sm)
        per_pass = (n_layers + 1) * l * stage
        return math.ceil(batch / 2) * per_pass
    per_layer = encoder_layer_latency_s(hw, l, d, softmax_mode=softmax_mode,
                                        pipelined=pipelined, d_ff=d_ff)
    return math.ceil(batch / 4) * n_layers * per_layer


def end_to_end_tops(hw: Hardware, n_layers: int, l: int, d: int, d_ff: int,
                    *, pipelined: str = "fine",
                    softmax_mode: str = "hastily",
                    batch: int = 1) -> float:
    ops = batch * bert_ops(n_layers, l, d, d_ff)
    t = end_to_end_latency_s(hw, n_layers, l, d, d_ff, pipelined=pipelined,
                             softmax_mode=softmax_mode, batch=batch)
    return ops / t / 1e12


def node_power_w(hw: Hardware, tops: float) -> float:
    """P = idle floor + e_op-proportional dynamic power (Fig 13's
    model-size-invariant TOPS/W falls out of this form)."""
    return hw.p_idle + tops * 1e12 * hw.e_op


def tops_per_watt(hw: Hardware, n_layers: int, l: int, d: int, d_ff: int,
                  *, batch: int = 1) -> float:
    t = end_to_end_tops(hw, n_layers, l, d, d_ff, batch=batch)
    return t / node_power_w(hw, t)


# --------------------------------------------------------------------------
# kernel roofline: q-block-tiled varlen paged attention
# --------------------------------------------------------------------------
#
# The serving-kernel analogue of the figures above: instead of crossbar
# stages, a bytes-moved/FLOPs roofline over the page-walk grid that
# ``kernels/autotune.py`` scores tile candidates against.  One varlen step
# is a set of lane segments (n_new tokens landing on kv_len live rows);
# tiling with q-blocks of Bq rows turns "read each page once per token"
# into "once per block" — the model counts exactly that.

@dataclasses.dataclass(frozen=True)
class PlatformSpec:
    """Roofline constants of the machine running the *serving kernels*.

    Not Table II — the jnp scan / Pallas kernel run on a host CPU or a TPU,
    and the tuner needs their balance point, not HASTILY's.  Numbers are
    order-of-magnitude (a tile choice flips on ratios, not absolutes).
    """
    name: str
    mem_bw_gbs: float        # sustained bytes/s feeding the kernel
    flops: float             # peak f32 FLOP/s
    dispatch_ns: float       # fixed cost per page-walk grid step
    dequant_page_ns: float   # extra per-page cost of page-granular dequant


PLATFORMS: Dict[str, PlatformSpec] = {
    # host CPU running the jnp page-block scan (XLA:CPU, ~1 socket)
    "cpu": PlatformSpec("cpu", mem_bw_gbs=40.0, flops=2e11,
                        dispatch_ns=400.0, dequant_page_ns=200.0),
    # one TPU core running the Pallas scalar-prefetch kernel; per-page
    # dequant is free there (the kernel dequantises each KV row in place)
    "tpu": PlatformSpec("tpu", mem_bw_gbs=1.2e3, flops=2e14,
                        dispatch_ns=120.0, dequant_page_ns=0.0),
}


def platform_spec(name: str) -> PlatformSpec:
    """The row for ``name``; an unknown platform is an error, not a default
    (a TPU scored with CPU constants would tune for the wrong machine)."""
    if name not in PLATFORMS:
        raise KeyError(f"no perfmodel platform {name!r}; known: "
                       f"{sorted(PLATFORMS)}")
    return PLATFORMS[name]


def varlen_attention_traffic(segments, *, block_q: int, block_pages: int,
                             page_size: int, hq: int, hkv: int, head_dim: int,
                             kv_bytes: int = 4,
                             scaled: bool = False) -> Dict[str, float]:
    """Bytes moved / FLOPs / grid steps of one tiled varlen step.

    ``segments``: iterable of ``(n_new, kv_len)`` lane chunks (kv_len counts
    the new rows).  ``block_q = 1`` is the untiled batch = T dataflow.  KV
    bytes dominate: every q-block walks its lane's live pages, so pages are
    read ``ceil(n/Bq)`` times per lane instead of ``n`` — the tiling win the
    autotuner is shopping for.  ``scaled`` adds the int8 dequant-scale
    planes (4 bytes/row alongside ``kv_bytes``/elem rows).
    """
    bq = max(1, int(block_q))
    bp = max(1, int(block_pages))
    row_bytes = 2 * head_dim * kv_bytes * hkv        # K + V, all kv heads
    if scaled:
        row_bytes += 2 * 4 * hkv                     # k_scale + v_scale rows
    bytes_kv = bytes_q = flops = steps = pages = 0.0
    for n_new, kv_len in segments:
        n_new = int(n_new)
        kv_len = int(kv_len)
        if n_new <= 0:
            continue
        nb = -(-n_new // bq)
        for j in range(nb):
            rows = min(bq, n_new - j * bq)
            kv_blk = kv_len - n_new + j * bq + rows  # block's causal horizon
            p_live = -(-kv_blk // page_size)
            pages += p_live
            bytes_kv += p_live * page_size * row_bytes
            bytes_q += 2 * rows * hq * head_dim * 4  # q read + out write
            flops += 4.0 * rows * (p_live * page_size) * hq * head_dim
            steps += -(-p_live // bp)
    return {"bytes_kv": bytes_kv, "bytes_q": bytes_q, "flops": flops,
            "grid_steps": steps, "pages_read": pages,
            "bytes_total": bytes_kv + bytes_q}


def varlen_attention_roofline(spec: PlatformSpec, traffic: Dict[str, float],
                              *, block_pages: int = 1,
                              dequant: str = "block") -> float:
    """Predicted step seconds: max(bytes/BW, flops/peak) + grid overheads."""
    t_mem = traffic["bytes_total"] / (spec.mem_bw_gbs * 1e9)
    t_cmp = traffic["flops"] / spec.flops
    t_grid = traffic["grid_steps"] * spec.dispatch_ns * 1e-9
    if dequant == "page" and block_pages > 1:
        t_grid += traffic["pages_read"] * spec.dequant_page_ns * 1e-9
    return max(t_mem, t_cmp) + t_grid


# --------------------------------------------------------------------------
# headline claim summary (used by benchmarks + tests)
# --------------------------------------------------------------------------

BERT_BASE = dict(n_layers=12, d=768, d_ff=3072, heads=12)
BERT_LARGE = dict(n_layers=24, d=1024, d_ff=4096, heads=16)


def headline_numbers(hw: Hardware = DEFAULT_HW) -> Dict[str, float]:
    base = dict(l=512)
    out = {
        "softmax_puma_8192_w16_us":
            softmax_latency_s(hw, 8192, "puma", 16) * 1e6,
        "softmax_uclm_8192_w16_us":
            softmax_latency_s(hw, 8192, "uclm", 16) * 1e6,
        "softmax_multicore_8192_w16_us":
            softmax_latency_s(hw, 8192, "multicore", 16) * 1e6,
        "softmax_w64_gain_pct":
            100 * (1 - softmax_latency_s(hw, 8192, "multicore", 64)
                   / softmax_latency_s(hw, 8192, "multicore", 16)),
        "softmax_energy_ratio_puma_4096":
            softmax_energy_j(hw, 4096, "puma")
            / softmax_energy_j(hw, 4096, "multicore"),
        "tops_bert_base": end_to_end_tops(
            hw, BERT_BASE["n_layers"], 512, BERT_BASE["d"],
            BERT_BASE["d_ff"], batch=2),
        "tops_bert_large": end_to_end_tops(
            hw, BERT_LARGE["n_layers"], 512, BERT_LARGE["d"],
            BERT_LARGE["d_ff"], batch=2),
        "tops_puma_bert_base": end_to_end_tops(
            hw, BERT_BASE["n_layers"], 512, BERT_BASE["d"],
            BERT_BASE["d_ff"], pipelined="coarse", softmax_mode="puma",
            batch=1),
        "tops_w_hastily": tops_per_watt(
            hw, BERT_BASE["n_layers"], 512, BERT_BASE["d"],
            BERT_BASE["d_ff"], batch=2),
        "gpu_tops_bert_base": GPU.tops_bert_base_b1,
        "softmax_frac_puma_1024":
            softmax_fraction(hw, 1024, 768, "puma"),
        "softmax_frac_hastily_1024":
            softmax_fraction(hw, 1024, 768, "hastily"),
    }
    out["speedup_tops_vs_gpu_base"] = (out["tops_bert_base"]
                                       / GPU.tops_bert_base_b1)
    out["tops_w_vs_gpu_b1"] = out["tops_w_hastily"] / GPU.tops_w_b1
    out["tops_w_vs_gpu_b4"] = out["tops_w_hastily"] / GPU.tops_w_b4
    return out
