"""Faults planted in the program, for the checks that ``correct`` has to
catch.  While the context is open:

- ``state_unchanged``: the model's ragged step hands back the KV pool it
  was given, so no row is ever written;
- ``token_altered``: every token the step picks is replaced by the next id;
- ``top_p_ignored``: the engine samples every sampled request over the
  whole vocabulary, as if the request had set no ``top_p``;
- ``temperature_one``: the engine samples every sampled request at
  temperature 1 (its ``top_p`` kept).

The last two alter what ``EngineCore.submit`` hands the scheduler, so the
step's program (and its compile) is the one a sound run uses.
"""
from __future__ import annotations

import contextlib
import dataclasses

KINDS = ("state_unchanged", "token_altered", "top_p_ignored",
         "temperature_one")


@contextlib.contextmanager
def planted(kind: str):
    import jax.numpy as jnp
    import repro.serving.core as core
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}")
    build, submit = core.build_model, core.EngineCore.submit

    def faulty(cfg):
        m = build(cfg)
        step = m.step_ragged

        def f(params, toks, caches, *args, **kw):
            out, new = step(params, toks, caches, *args, **kw)
            if kind == "state_unchanged":
                return out, caches
            return jnp.where(out >= 0, (out + 1) % cfg.vocab_size, out), new

        return dataclasses.replace(m, step_ragged=f)

    def loose(self, req, *args, **kw):
        sp = req.sampling
        if not sp.greedy:
            sp = (dataclasses.replace(sp, top_p=None)
                  if kind == "top_p_ignored"
                  else dataclasses.replace(sp, temperature=1.0))
            req.sampling, req.temperature = sp, sp.temperature
        return submit(self, req, *args, **kw)

    if kind in ("state_unchanged", "token_altered"):
        core.build_model = faulty
    else:
        core.EngineCore.submit = loose
    try:
        yield
    finally:
        core.build_model, core.EngineCore.submit = build, submit
