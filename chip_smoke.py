#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.  Not a benchmark.

Serves deepseek-7b at its published widths and full depth (30 layers,
d_model 4096, 32 MHA heads of 128, d_ff 11008, vocab 102400) with random
params drawn from ``--seed``, through the entry points a user calls:
``init_params`` → ``EngineCore`` (ragged step, bf16 page pool, LUT exp,
the committed autotune row) → ``AsyncLMServer``.  Phases:

1. params   built on the chip by one jitted program;
2. serve    8 seeded greedy requests (prompts of 128–512 tokens, 32 new
            tokens each); every request finishes with 32 in-vocab tokens,
            and no step saw a non-finite logit row (the engine raises);
3. graph    the compiled ragged step holds the Pallas varlen kernel
            (``tpu_custom_call``);
4. kernel   the varlen paged kernel against ``paged_attention_reference``
            at deepseek widths, bf16 and int8 pools × lut and exact exp.

``--four-chips`` runs only the tensor-parallel phase over four chips: the
same requests at ``EngineCore(mesh=4)`` and on one chip, token for token.

Every failure exits non-zero.  The last line of stdout, and only on
success, is ``{"ok": true, "device": {"platform": "tpu", ...}}``.  There is
no CPU path: without a TPU the script exits 1.

    python3 chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "deepseek-7b"
REQUESTS = 8
PROMPT_LENS = (128, 512)
MAX_NEW = 32
# Engine sizing for one v5e (16 GiB HBM).  The 13.8 GB of bf16 weights
# leave room for a pool of 4 lanes × 34 pages × 16 rows (2176 rows, 1.07
# GB at 491,520 B per row) plus the step's working set.  Two stream
# widths (decode-only and mixed) keep the step to a few compiles.
LANES = 4
PAGE_SIZE = 16
MAX_LEN = PROMPT_LENS[1] + MAX_NEW
NUM_PAGES = LANES * -(-MAX_LEN // PAGE_SIZE)
CHUNK = 512
# Kernel phase: worst |kernel - reference| over the output (bf16 rows of
# softmax-weighted N(0, 1) values, so |out| < 1).  Both sides use the same
# exp; they differ in summation order and in the precision of the
# chip's f32 matmul passes.
KERNEL_ATOL = 2e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips; JAX found {len(devs)}")
    return devs[0]


class CompileClock:
    """Seconds the backend spent compiling, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


def make_requests(cfg, seed: int):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, REQUESTS)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, int(n)
                                               ).astype(np.int32),
                    max_new=MAX_NEW)
            for i, n in enumerate(lens)]


def build_engine(cfg, params, mesh=None):
    from repro.serving import EngineCore
    return EngineCore(cfg, params, lanes=LANES, page_size=PAGE_SIZE,
                      num_pages=NUM_PAGES, chunk_size=CHUNK,
                      max_len=MAX_LEN, token_buckets=(LANES, LANES + CHUNK),
                      mesh=mesh)


def serve(eng, reqs):
    """Stream every request through AsyncLMServer → {uid: tokens}."""
    from repro.serving import AsyncLMServer

    async def main():
        async with AsyncLMServer(eng) as server:
            async def client(r):
                return [t async for t in server.generate(r)]
            return await asyncio.gather(*(client(r) for r in reqs))

    streams = asyncio.run(main())
    return {r.uid: s for r, s in zip(reqs, streams)}


def check_streams(cfg, reqs, streams) -> int:
    for r in reqs:
        got = streams[r.uid]
        if len(got) != MAX_NEW or got != list(r.tokens):
            raise AssertionError(
                f"request {r.uid}: streamed {len(got)} tokens, expected "
                f"{MAX_NEW} matching the engine's record")
        if not all(0 <= t < cfg.vocab_size for t in got):
            raise AssertionError(f"request {r.uid}: token outside vocab")
    return sum(len(s) for s in streams.values())


def mem_stat(dev, stat: str = "peak_bytes_in_use") -> int:
    return int(dev.memory_stats()[stat])


def phase_params(cfg, seed, dev):
    import jax
    from repro.models import init_params
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, seed))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"params: {nbytes} bytes in {time.perf_counter() - t0:.2f}s "
        f"(layers {cfg.num_layers} of {cfg.num_layers}, no depth cut); "
        f"peak_bytes_in_use {mem_stat(dev)}")
    return params


def phase_serve(cfg, params, seed, dev):
    eng = build_engine(cfg, params)
    log(f"engine: lanes {LANES}, page {PAGE_SIZE}, {NUM_PAGES} pages, "
        f"chunk {CHUNK}, kv {'int8' if cfg.kv_quant else cfg.dtype}, "
        f"exp {cfg.exp_mode}, kernel {eng.kernel_config.describe()}")
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    streams = serve(eng, reqs)
    dt = time.perf_counter() - t0
    tokens = check_streams(cfg, reqs, streams)
    log(f"serve: {len(reqs)} requests (prompts "
        f"{[len(r.prompt) for r in reqs]}) finished, {tokens} tokens in "
        f"{int(eng.obs.c_steps.value())} steps, {dt:.2f}s wall including "
        f"compiles, "
        f"{eng.trace_count} step traces; non-finite logit rows: 0; "
        f"peak_bytes_in_use {mem_stat(dev)}")
    return eng


def phase_graph(eng):
    hlo = eng.compiled_step_hlo()
    n = hlo.count("tpu_custom_call")
    if n == 0:
        raise AssertionError("compiled ragged step holds no tpu_custom_call")
    log(f"graph: compiled ragged step holds {n} tpu_custom_call op(s)")


def kernel_case(rng, kv_dtype: str, exp_mode: str) -> float:
    """Max |kernel − reference| for one packed stream at deepseek widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.streaming_attention import quantize_kv_rows
    from repro.kernels.paged_attention import (
        paged_attention_varlen, paged_attention_varlen_reference,
        varlen_positions)
    h, d, ps = 32, 128, PAGE_SIZE
    nq = np.array([1, 1, 37, 80])                 # decodes + prefill chunks
    kv = np.array([100, 300, 200, 513])           # live rows after the step
    cu = np.concatenate([[0], np.cumsum(nq)]).astype(np.int32)
    lane_pages = -(-kv // ps)
    width = int(lane_pages.max())
    n = int(lane_pages.sum())
    perm = rng.permutation(n)
    tables = np.full((len(nq), width), n, np.int32)   # page n: scratch
    off = 0
    for i, p in enumerate(lane_pages):
        tables[i, :p] = perm[off:off + p]
        off += p
    token_pages = jnp.asarray(np.repeat(tables, nq, axis=0))
    q_pos = jnp.asarray(varlen_positions(cu, kv))
    q = jnp.asarray(rng.normal(size=(int(cu[-1]), h, d)), jnp.bfloat16)
    pools = [rng.normal(size=(n + 1, h, ps, d)).astype(np.float32)
             for _ in range(2)]
    kw = dict(cu_seqlens=cu, exp_mode=exp_mode, block_q=32)
    if kv_dtype == "int8":
        quant = [quantize_kv_rows(jnp.asarray(p).reshape(1, -1, ps, d))
                 for p in pools]
        kp, vp = (v.reshape(n + 1, h, ps, d) for v, _ in quant)
        kw.update(k_scale=quant[0][1].reshape(n + 1, h, ps),
                  v_scale=quant[1][1].reshape(n + 1, h, ps))
    else:
        kp, vp = (jnp.asarray(p, jnp.bfloat16) for p in pools)
    got = paged_attention_varlen(q, kp, vp, token_pages, q_pos,
                                 interpret=False, **kw)
    with jax.default_matmul_precision("highest"):
        want = paged_attention_varlen_reference(q, kp, vp, token_pages,
                                                q_pos, **kw)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"kernel {kv_dtype}/{exp_mode}: non-finite")
    return float(np.abs(got - want).max())


def phase_kernel(seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    worst = {}
    for kv_dtype in ("bf16", "int8"):
        for exp_mode in ("lut", "exact"):
            worst[f"{kv_dtype}/{exp_mode}"] = kernel_case(rng, kv_dtype,
                                                         exp_mode)
    log(f"kernel: varlen paged kernel vs reference, max abs error "
        f"{worst} (bound {KERNEL_ATOL})")
    bad = {k: v for k, v in worst.items() if not v <= KERNEL_ATOL}
    if bad:
        raise AssertionError(f"kernel error over {KERNEL_ATOL}: {bad}")


def first_divergence(reqs, a, b):
    for r in reqs:
        for i, (x, y) in enumerate(zip(a[r.uid], b[r.uid])):
            if x != y:
                return r, i
    return None


def logit_gaps(cfg, params, req, i, tokens, picks):
    """At the diverging position, from a plain forward on chip 0's replica
    of the params: top-1 minus top-2 logit, and logit[a] - logit[b] for the
    two picks (a, b)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.lm import lm_apply
    local = jax.tree.map(lambda x: x.addressable_shards[0].data, params)
    seq = np.concatenate([req.prompt, np.asarray(tokens[:i], np.int32)])
    logits, _, _ = lm_apply(cfg, local, jnp.asarray(seq)[None])
    row = np.asarray(logits[0, -1], np.float32)
    top = np.sort(row)[-2:]
    return float(top[1] - top[0]), float(row[picks[0]] - row[picks[1]])


def phase_four_chips(cfg, seed):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    devs = jax.devices()[:4]
    params = jax.block_until_ready(init_params(cfg, seed))
    reqs = make_requests(cfg, seed)
    one = serve(build_engine(cfg, params), reqs)
    check_streams(cfg, reqs, one)
    # 13.8 GB of weights is replicated per chip at mesh 4: the one-chip
    # engine and its params must be gone from chip 0 first.
    del params
    gc.collect()
    log(f"mesh 1: {REQUESTS} requests served; chip 0 holds "
        f"{mem_stat(devs[0], 'bytes_in_use')} bytes after release")
    mesh = make_mesh((4,), ("model",))
    params = jax.block_until_ready(init_params(cfg, seed, mesh=mesh))
    reqs4 = make_requests(cfg, seed)
    four = serve(build_engine(cfg, params, mesh=mesh), reqs4)
    check_streams(cfg, reqs4, four)
    div = first_divergence(reqs, one, four)
    if div is not None:
        r, i = div
        picks = (one[r.uid][i], four[r.uid][i])
        same = sum(a == b for u in one for a, b in zip(one[u], four[u]))
        log(f"mesh 4 diverges from mesh 1 at request {r.uid} token {i}: "
            f"{picks[0]} vs {picks[1]}; {same} of "
            f"{sum(map(len, one.values()))} tokens agree position-wise")
        top2, gap = logit_gaps(cfg, params, r, i, one[r.uid], picks)
        raise AssertionError(
            f"mesh 4 diverges from mesh 1 at request {r.uid} token {i}; "
            f"one-chip forward there: top-1 minus top-2 logit {top2}, "
            f"logit[{picks[0]}] - logit[{picks[1]}] = {gap}")
    log(f"mesh 4: {REQUESTS} requests, {sum(map(len, four.values()))} "
        f"tokens identical to mesh 1; peak_bytes_in_use per chip "
        f"{[mem_stat(d) for d in devs]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-4 vs mesh-1 identity phase")
    args = ap.parse_args()

    count = 4 if args.four_chips else 1
    dev = require_tpu(count)
    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    cfg = get_config(ARCH)
    log(f"smoke run, not a benchmark: {dev.device_kind}, {ARCH}, seed "
        f"{args.seed}, compile cache {cache}")

    if args.four_chips:
        phase_four_chips(cfg, args.seed)
    else:
        params = phase_params(cfg, args.seed, dev)
        eng = phase_serve(cfg, params, args.seed, dev)
        phase_graph(eng)
        del eng, params
        gc.collect()
        phase_kernel(args.seed)
    log(f"compile: {clock.count} backend compiles, {clock.seconds:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
