"""The measured path: warm-up, the serving window, and what it recorded.

The window drives the program's own entry, ``AsyncLMServer.generate`` over
``EngineCore.step``.  The benchmark wraps two public calls to record spans
of its own: ``EngineCore.step`` (host clock, and a ``TraceAnnotation`` for
the profiler) and ``Scheduler.pack`` (the step's packed positions, from
which the kernel's needed work is counted).
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from benchlib.traffic import Item


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    pos: Optional[np.ndarray]      # live tokens' absolute positions
    cu: Optional[np.ndarray]       # lane boundaries over them
    width: int                     # rows the step computed
    committed: int                 # tokens the step served


@dataclasses.dataclass
class Rec:
    item: object                   # traffic.Item
    due: Optional[float] = None    # perf_counter when it was due (sent)
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


class StepLog:
    """Spans around ``EngineCore.step`` and ``Scheduler.pack``."""

    def __init__(self, eng):
        import jax
        self.steps: List[Step] = []
        self._pack = None
        annotate = jax.profiler.TraceAnnotation
        step = eng.step

        def traced_step():
            t0 = time.perf_counter()
            with annotate("engine.step"):
                out = step()
            t1 = time.perf_counter()
            pos, cu = self._pack or (None, None)
            self._pack = None
            self.steps.append(Step(t0, t1, pos, cu, int(out.padded_rows),
                                   len(out.tokens)))
            return out

        eng.step = traced_step
        sched = getattr(eng, "scheduler", None)
        pack = getattr(sched, "pack", None)
        if pack is not None:
            def traced_pack(plans):
                with annotate("scheduler.pack"):
                    b = pack(plans)
                self._pack = (np.array(b.pos[:b.live]),
                              np.array(b.cu_seqlens))
                return b
            sched.pack = traced_pack


def _request(item, uid: int):
    from repro.serving import Request
    from repro.serving.sampling import SamplingParams
    sp = (SamplingParams() if item.greedy else
          SamplingParams(temperature=item.temperature, top_p=item.top_p,
                         seed=item.seed))
    return Request(uid=uid, prompt=item.prompt, max_new=item.max_new,
                   sampling=sp)


def warm(eng, engine_cfg: dict, vocab: int, seed: int,
         lone: bool = False) -> int:
    """Compile (or load) every step shape the cell's traffic uses, before
    the window: first one request of whole chunks, the most that fit in
    ``max_len``, which takes the page-table width (a power of two of
    pages) to its largest, then one step at each of
    the configuration's ``token_buckets``, each made of sampled requests
    whose prompts sum to the bucket; with ``lone`` (open loops, where a
    request can run alone) also the one-row step that the scheduler always
    keeps.  Returns the steps run.
    """
    rng = np.random.default_rng(seed)
    lanes = engine_cfg["lanes"]
    chunk, max_len = engine_cfg["chunk_size"], engine_cfg["max_len"]
    uid = 1 << 40
    longest = (max_len - 1) // chunk * chunk
    assert 2 * longest > max_len, (longest, max_len)
    groups = [[longest]]
    for w in sorted(set(engine_cfg["token_buckets"]) | ({1} if lone else set())):
        parts = [chunk] * (w // chunk) + ([w % chunk] if w % chunk else [])
        assert len(parts) <= lanes, (w, parts)
        groups.append(parts)
    steps = 0
    for parts in groups:
        for n in parts:
            uid += 1
            eng.submit(_request(Item(
                uid=uid, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                max_new=1, temperature=0.7, top_p=0.9, seed=uid % 997), uid))
        while eng.scheduler.has_work():
            eng.step()
            steps += 1
    return steps


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    recs: List[Rec]
    late_ms_max: float             # how late the open-loop sender ran


async def _serve(eng, items, traffic: dict, seconds: float, on_open,
                 on_close) -> Window:
    from repro.serving import AsyncLMServer
    server = AsyncLMServer(eng, max_waiting=len(items) + 1024)
    await server.start()
    recs: Dict[int, Rec] = {}
    tasks: List[asyncio.Task] = []
    first = asyncio.Event()
    late = [0.0]

    async def one(item):
        rec = recs[item.uid] = Rec(item)
        if item.due is None:            # closed loop: due when it is sent
            rec.due = time.perf_counter()
        try:
            async for tok in server.generate(_request(item, item.uid)):
                rec.times.append(time.perf_counter())
                rec.tokens.append(int(tok))
                if len(rec.tokens) == 1:
                    first.set()
            rec.done = True
        except asyncio.CancelledError:
            raise
        except Exception as e:               # served wrong: counts failed
            rec.error = f"{type(e).__name__}: {e}"
        return rec

    if traffic["loop"] == "closed":
        queue = iter(items)

        async def client(item):
            while item is not None:
                await one(item)
                item = next(queue, None)

        wave = [next(queue) for _ in range(int(traffic["clients"]))]
        tasks = [asyncio.create_task(client(it)) for it in wave]
        while not all(it.uid in recs and recs[it.uid].tokens for it in wave):
            first.clear()
            await first.wait()
        t_open = on_open()
        await asyncio.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    else:
        # Dues count from the window's opening; the pre-roll's are negative.
        base = time.perf_counter() - min(0.0, min(it.due for it in items))

        async def send():
            for item in items:
                due = base + item.due
                await asyncio.sleep(max(0.0, due - time.perf_counter()))
                late[0] = max(late[0], time.perf_counter() - due)
                tasks.append(asyncio.create_task(one(item)))

        sender = asyncio.create_task(send())
        await asyncio.sleep(max(0.0, base - time.perf_counter()))
        t_open = on_open()
        await asyncio.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        sender.cancel()
    t_close = on_close()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await server.shutdown(drain=False)
    for item in items:          # due in the window: sent or not, it counts
        if item.due is not None and item.due <= seconds:
            recs.setdefault(item.uid, Rec(item)).due = base + item.due
    return Window(t_open, t_close, list(recs.values()), late[0] * 1e3)


def serve_window(eng, items, traffic: dict, seconds: float, on_open,
                 on_close) -> Window:
    return asyncio.run(_serve(eng, items, traffic, seconds, on_open,
                              on_close))
