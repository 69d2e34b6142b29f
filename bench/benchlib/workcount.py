"""Operations and bytes that the work needs, from shapes alone.

These count what attention and the model need, whatever implements them:
no tiling, padding or recomputation is counted.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def lane_contexts(pos: Sequence[int], cu: Sequence[int]) -> np.ndarray:
    """Each lane's KV length after a step: its last live position + 1.
    ``pos`` are the live tokens' absolute positions, ``cu`` the lane
    boundaries (S+1,) over them."""
    pos = np.asarray(pos, np.int64)
    cu = np.asarray(cu, np.int64)
    ends = cu[1:][cu[1:] > cu[:-1]]
    return pos[ends - 1] + 1


def attention_flops(a, pos) -> float:
    """4 * sum over new rows of (causal KV horizon * hq * d_head), all
    layers: QK^T and PV, two operations per multiply-add."""
    horizon = np.asarray(pos, np.float64) + 1.0
    return 4.0 * float(horizon.sum()) * a.hq * a.dh * a.layers


def attention_bytes(a, pos, cu, kv_itemsize: float, act_itemsize: float,
                    kv_scale_bytes: float = 0.0) -> float:
    """Each live KV row of each lane's context read once (K and V, plus
    per-row scales for a quantised pool), q read and the output written
    once at the activation dtype; all layers."""
    ctx = float(lane_contexts(pos, cu).sum())
    kv = ctx * a.hkv * 2 * (a.dh * kv_itemsize + kv_scale_bytes)
    qo = len(pos) * a.hq * a.dh * 2 * act_itemsize
    return (kv + qo) * a.layers


def matmul_params(a) -> int:
    """Non-embedding matmul parameters of the trunk."""
    attn = a.d * (a.hq + 2 * a.hkv) * a.dh + a.hq * a.dh * a.d
    mlp = (3 if a.gated else 2) * a.d * a.f
    return a.layers * (attn + mlp)


def model_flops(a, pos, logit_rows: int) -> float:
    """2 * matmul params per live token, 2 * d * vocab per row whose logits
    a served token needs, plus the attention operations."""
    return (2.0 * matmul_params(a) * len(pos)
            + 2.0 * a.d * a.vocab * logit_rows + attention_flops(a, pos))


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak_flops, nbytes / peak_bw)
