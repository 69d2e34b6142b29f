"""One generator for every traffic mix.

A mix is a JSON file of parameters under ``bench/traffic/``: the loop kind
(``closed`` with a client count, or ``open`` with a rate in requests per
second), prompt and output length distributions, and the sampling mix.
Every seed gets the same multiset of sizes, gaps and sampling kinds (the
distributions' quantiles), in an order and with token ids drawn from the
seed: the seed changes which request comes when, never how much work a
run holds.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request of a run, as the client will send it."""
    uid: int
    prompt: np.ndarray            # (Lp,) int32
    max_new: int
    temperature: float
    top_p: Optional[float]
    seed: int                     # the request's sampling seed (uint32)
    due: Optional[float] = None   # open loop: seconds after the window opens

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _kinds(sampling: List[dict], n: int, rng) -> List[dict]:
    """The sampling mix as ``n`` per-request records, shares rounded."""
    counts = [int(round(s["share"] * n)) for s in sampling]
    counts[-1] = n - sum(counts[:-1])
    out = [s for s, c in zip(sampling, counts) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def _items(traffic: dict, n: int, vocab: int, rng, dues=None,
           order=None) -> List[Item]:
    """``n`` requests: quantile sizes and sampling kinds, in an order drawn
    from ``order`` (a generator; the seed's own when not given), token ids
    and sampling seeds from ``rng``."""
    order = rng if order is None else order
    plens = quantiles(traffic["prompt"], n)[order.permutation(n)]
    olens = quantiles(traffic["output"], n)[order.permutation(n)]
    kinds = _kinds(traffic["sampling"], n, order)
    items = []
    for i in range(n):
        k = kinds[i]
        items.append(Item(
            uid=i, prompt=rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
            max_new=int(olens[i]), temperature=float(k.get("temperature", 0.0)),
            top_p=k.get("top_p"), seed=int(rng.integers(0, 2 ** 32)),
            due=None if dues is None else float(dues[i])))
    return items


def build(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The run's requests in the order the clients send them.

    Open loop: ``round(rate * span)`` arrivals whose gaps are the
    exponential distribution's quantiles (Poisson arrivals), permuted, over
    a span of ``preroll_s`` before the window (set-up: it brings the server
    to its steady load) and the window's ``seconds``; ``due`` is in seconds
    from the window's opening, negative in the pre-roll.
    Closed loop: ``requests`` items that the clients take in turn, their
    sizes in one fixed order (a window uses only the first few, so a
    seed-drawn order would change the work); with ``first_wave: residual``
    the first ``clients`` items start part-way through their output (the
    generated part joins the prompt), so the window opens on a steady mix
    of context lengths, and the seed deals them over the clients (with
    ``order: fixed``, every seed sends them in one order).
    """
    rng = np.random.default_rng(seed)
    if traffic["loop"] == "open":
        rate = float(traffic["rate"])
        pre = float(traffic.get("preroll_s", 0.0))
        span = pre + seconds
        n = max(1, int(round(rate * span)))
        u = (np.arange(n) + 0.5) / n
        gaps = (-np.log1p(-u) / rate)[rng.permutation(n)]
        dues = np.cumsum(gaps) - gaps[0]
        dues *= min(1.0, span / (dues[-1] + gaps[-1]))
        return _items(traffic, n, vocab, rng, dues - pre)
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop kind {traffic['loop']!r}")
    fixed = np.random.default_rng(0)
    items = _items(traffic, int(traffic["requests"]), vocab, rng,
                   order=fixed)
    if traffic.get("first_wave") == "residual":
        # The first wave is one fixed set of (prompt, output, share done)
        # triples, paired by a seed-independent permutation; the seed
        # only deals it over the clients.
        c = int(traffic["clients"])
        plens = quantiles(traffic["prompt"], c)
        olens = quantiles(traffic["output"], c)[fixed.permutation(c)]
        frac = ((np.arange(c) + 0.5) / c)[fixed.permutation(c)]
        deal = (np.arange(c) if traffic.get("order") == "fixed"
                else rng.permutation(c))
        for it, k in zip(items[:c], deal):
            done = min(int(math.floor(frac[k] * olens[k])), int(olens[k]) - 1)
            it.prompt = rng.integers(0, vocab, int(plens[k]) + done,
                                     dtype=np.int32)
            it.max_new = int(olens[k]) - done
    return items
