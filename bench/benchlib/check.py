"""What decides ``correct``: served tokens against the reference.

After the window, a sample of requests drawn from the seed (always with
the greedy one that received the most tokens) is run through the plain
float32 reference over ``prompt ⊕ served tokens``.  At each served token:

- greedy requests: the gap is the reference's best logit minus its logit
  for the served token: 0 where the program picked what the reference
  picks, and small where the two differ on a near-tie (``gap_max``,
  ``gap_mean``);
- sampled requests: ``above`` is the reference's probability mass, at the
  request's temperature, of the tokens ranked strictly above the served
  one.  A sampler that keeps the nucleus ``top_p`` picks only tokens with
  ``above < top_p``; ``nucleus_excess_max`` is the largest ``above -
  top_p``, so it stays at or under 0 but for near-ties at the nucleus's
  edge, and reaches up to ``1 - top_p`` where the sampler ignores the
  nucleus or the temperature.

A configuration's ``correct`` entry gives the limit of each compared
number; a number with no served token to read is NaN and fails.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib import model


def pick(recs, seed: int, k: int) -> List:
    """Up to ``k`` requests that received tokens: the greedy one with the
    most tokens always among them, the rest drawn from the seed."""
    cands = sorted((r for r in recs if r.tokens and r.error is None),
                   key=lambda r: r.item.uid)
    if not cands:
        return []
    greedy = [r for r in cands if r.item.greedy] or cands
    longest = max(greedy, key=lambda r: (len(r.tokens), -r.item.uid))
    rest = [r for r in cands if r is not longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    chosen = [rest[i] for i in sorted(rng.permutation(len(rest))[:k - 1])]
    return [longest] + chosen


def numbers(a, w, recs, length: int, control: bool = False
            ) -> Dict[str, float]:
    """The compared numbers over the picked requests.  With ``control``
    the gaps are read at every served position, greedy or sampled, for the
    token that the reference's W8A8 forward puts first."""
    gaps, excess = [], []
    for r in recs:
        it = r.item
        gap, above = model.served_scores(a, w, it.prompt, r.tokens,
                                         it.temperature, length, control)
        if control or it.greedy:
            gaps.append(gap)
        else:
            top_p = 1.0 if it.top_p is None else float(it.top_p)
            excess.append(above - top_p)
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    e = np.concatenate(excess) if excess else np.zeros((0,))
    nan = float("nan")
    return {"gap_max": float(g.max()) if len(g) else nan,
            "gap_mean": float(g.mean()) if len(g) else nan,
            "tokens": int(len(g)),
            "nucleus_excess_max": float(e.max()) if len(e) else nan,
            "nucleus_outside": int(np.sum(e >= 0.0)),
            "sampled_tokens": int(len(e))}


def kv_bits_lost(pool, stated_dtype: str) -> int:
    """Bits by which the KV pool's narrowest leaf falls short of the
    configuration's stated dtype (0 when it holds that dtype)."""
    import jax
    import jax.numpy as jnp
    bits = min(np.dtype(x.dtype).itemsize * 8 for x in jax.tree.leaves(pool))
    return max(0, np.dtype(jnp.dtype(stated_dtype)).itemsize * 8 - bits)


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every limited number finite and at or under its limit."""
    return all(np.isfinite(nums[k]) and nums[k] <= v
               for k, v in limits.items())
