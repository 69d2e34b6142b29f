"""End-to-end metrics, from the host clock at the client side.

- ``output_tok_s``: output tokens the clients received inside the window,
  over the window's seconds;
- ``ttft_p<q>_ms``: percentile ``q`` of the time from each request's due
  time to its first token, over every request due in the window; one with
  no first token when the window closes counts at the close;
- ``itl_p<q>_ms``: percentile ``q`` of every gap between consecutive output
  tokens of a request, both inside the window;
- ``setup_s``: process start to window open.
"""
from __future__ import annotations

import re
from typing import List, Optional

import numpy as np


def ttfts_ms(win) -> List[float]:
    out = []
    for r in win.recs:
        if r.due is None or not (win.t_open <= r.due <= win.t_close):
            continue
        first = r.times[0] if r.times else win.t_close
        out.append((min(first, win.t_close) - r.due) * 1e3)
    return out


def itls_ms(win) -> List[float]:
    out = []
    for r in win.recs:
        t = np.asarray(r.times)
        t = t[(t >= win.t_open) & (t <= win.t_close)]
        out.extend((np.diff(t) * 1e3).tolist())
    return out


def output_tokens(win) -> int:
    return sum(int(np.sum((np.asarray(r.times) >= win.t_open)
                          & (np.asarray(r.times) <= win.t_close)))
               for r in win.recs)


def compute(name: str, win, setup_s: float) -> Optional[float]:
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        return output_tokens(win) / (win.t_close - win.t_open)
    m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
    if m:
        xs = ttfts_ms(win) if m.group(1) == "ttft" else itls_ms(win)
        return float(np.percentile(xs, int(m.group(2)))) if xs else None
    raise KeyError(f"no end-to-end metric {name!r}")
