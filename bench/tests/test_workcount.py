"""Work counts against numbers worked out by hand."""
import numpy as np
import pytest

from benchlib import workcount
from benchlib.model import Arch

A = Arch(d=8, layers=3, hq=2, hkv=1, dh=4, f=16, vocab=10, norm="rmsnorm",
         eps=1e-6, act="silu", gated=True, bias=False, tied=False, theta=1e4,
         init_std=0.02)
# One step: lane 0 decodes its 10th row (position 9), lane 1 prefills
# positions 0..2; an empty lane boundary repeats (a lane with no rows).
POS = [9, 0, 1, 2]
CU = [0, 1, 1, 4]


def test_lane_contexts():
    assert workcount.lane_contexts(POS, CU).tolist() == [10, 3]


def test_attention_flops():
    # horizons 10 + 1 + 2 + 3 = 16; 4 * 16 * hq 2 * dh 4 * layers 3
    assert workcount.attention_flops(A, POS) == 1536.0


def test_attention_bytes():
    # KV: contexts 13 rows * hkv 1 * (K, V) * dh 4 * 2 B = 208 per layer;
    # q in and out: 4 tokens * hq 2 * dh 4 * 2 B * 2 = 128 per layer.
    assert workcount.attention_bytes(A, POS, CU, 2, 2) == (208 + 128) * 3
    # int8 pool: 1 B per element plus a 4 B scale per row, head, K and V.
    assert workcount.attention_bytes(A, POS, CU, 1, 2, 4) == \
        (13 * 2 * (4 + 4) + 128) * 3


def test_model_flops():
    # per layer: q/k/v 8*(2+2)*4 = 128, o 2*4*8 = 64, MLP 3*8*16 = 384
    assert workcount.matmul_params(A) == 576 * 3
    # 2 * 1728 * 4 tokens + 2 * d 8 * vocab 10 * 2 rows + attention 1536
    assert workcount.model_flops(A, POS, 2) == 13824 + 320 + 1536


@pytest.mark.parametrize("flops,nbytes,want", [
    (197e12, 1.0, 1.0),          # compute-bound: 1 s at the bf16 peak
    (1.0, 819e9 * 2, 2.0),       # memory-bound: 2 s at 819 GB/s
])
def test_least_seconds(flops, nbytes, want):
    assert np.isclose(workcount.least_seconds(flops, nbytes, 197e12, 819e9),
                      want)
