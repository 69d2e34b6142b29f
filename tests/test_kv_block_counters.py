"""The attention walk's counters: ``attn_kv_blocks_walked_total`` and
``attn_kv_blocks_live_total`` count the (q_block, page_block) pairs of the
varlen kernel's grid, and the pairs holding a live KV row, from the packed
batch on the host.  They must agree with what ``q_block_layout`` — the
device-side block cut — implies."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.autotune import KernelConfig
from repro.kernels.paged_attention import q_block_layout, varlen_positions
from repro.kernels.paged_attention.varlen import kv_block_counts
from repro.serving import EngineCore, Request
from tests.test_engine_core import build, prompts_for


def _implied(cu, pos, t, bq, ps, p, bp):
    """(walked, live) from the device layout: NB blocks × ceil(P/bp) page
    blocks; a pair is live when its first row lies before the block's
    kv_len, cut at the table's end.  ``bq`` 1 is the untiled dispatch: one
    block per token, of length q_pos + 1."""
    if bq > 1:
        kv_len = np.asarray(q_block_layout(jnp.asarray(cu), jnp.asarray(pos),
                                           t, bq)[2])
    else:
        kv_len = np.asarray(pos) + 1
    bp = min(bp, p)
    nblk = -(-p // bp)
    first_row = np.arange(nblk) * bp * ps
    live = first_row[None, :] < np.minimum(kv_len, p * ps)[:, None]
    return len(kv_len) * nblk, int(live.sum())


# A hand-built stream of T = 32: decode lanes (1 row), a 13-row prefill
# chunk, a 2-row chunk, the bucket's dead padding rows as a pseudo-segment
# at position 0, then dead lanes as zero-width repeats of cu[-1].
CU = np.array([0, 1, 14, 15, 17, 20, 32, 32, 32], np.int32)
LENS = np.array([40, 13, 129, 75, 3, 12, 0, 0])


@pytest.mark.parametrize("block_pages,bq", [(1, 4), (2, 4), (8, 4), (8, 16),
                                            (None, 8), (3, 1)])
def test_counts_match_q_block_layout(block_pages, bq):
    t, ps, p = int(CU[-1]), 8, 20
    pos = varlen_positions(CU, LENS)
    pos[20:] = 0                              # dead rows sit at position 0
    bp = block_pages or 128 // ps
    walked, live = kv_block_counts(CU, pos, t, block_q=bq, page_size=ps,
                                   table_width=p, block_pages=block_pages)
    assert (walked, live) == _implied(CU, pos, t, bq, ps, p, bp)
    nb = t // bq + len(CU) - 1 if bq > 1 else t
    assert walked == nb * -(-p // min(bp, p))
    assert 0 < live <= walked


def test_counts_by_hand():
    """Bq 4, pages of 8, P 20, bp 8 → 3 page blocks of 64 rows.  Live
    q-blocks and their kv_len = q_pos[start] + 4: lane 0 one (39 + 4 = 43
    → 1 page block), lane 1 four (4, 8, 12, 16 → 1 each), lane 2 one
    (128 + 4 = 132 → 3), lane 3 one (73 + 4 = 77 → 2), lane 4 one (4 → 1),
    the padding three (4 → 1 each).  NB = 32 // 4 + 8 = 16, so five dead
    blocks pinned to kv_len 1 → 1 each."""
    pos = varlen_positions(CU, LENS)
    pos[20:] = 0
    walked, live = kv_block_counts(CU, pos, 32, block_q=4, page_size=8,
                                   table_width=20, block_pages=8)
    assert walked == 16 * 3
    assert live == 1 + 4 + 3 + 2 + 1 + 3 + 5


def test_engine_counts_each_ragged_step():
    """EngineCore's ragged step adds each step's (walked, live) to the
    registry, as q_block_layout implies for the batch it packed."""
    cfg, params = build()
    kc = KernelConfig(block_q=4, block_pages=2)
    eng = EngineCore(cfg, params, lanes=3, page_size=8, num_pages=24,
                     chunk_size=8, mode="ragged", kernel_config=kc)
    seen = []
    pack = eng.scheduler.batch_for

    def record(wants):
        batch, preempted = pack(wants)
        seen.append(batch)
        return batch, preempted

    eng.scheduler.batch_for = record
    for i, p in enumerate(prompts_for(cfg, 3, (3, 9, 14, 6))):
        eng.submit(Request(uid=i, prompt=p, max_new=5))
    while eng.scheduler.has_work():
        eng.step()
    want_walked = want_live = 0
    for batch in seen:
        if not batch.plans:
            continue
        cu = np.full((eng.lanes + 2,), batch.width, np.int32)
        cu[:len(batch.cu_seqlens)] = batch.cu_seqlens
        t = batch.width
        w, lv = _implied(cu, batch.pos, t, min(kc.block_q, t), 8,
                         batch.table.shape[1], kc.block_pages)
        want_walked += w
        want_live += lv
    reg = eng.obs.registry
    assert want_live > 0
    assert reg.value("attn_kv_blocks_walked_total") == want_walked
    assert reg.value("attn_kv_blocks_live_total") == want_live
