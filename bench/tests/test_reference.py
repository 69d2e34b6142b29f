"""The plain float32 reference against the program's own step, at small
widths on the CPU: a prefill chunk and then a decode step through the
paged cache, for both published families (llama: RMSNorm, gated SiLU,
MHA, untied head; starcoder2: LayerNorm, tanh-GELU, biases, GQA, tied
head).  Logits are compared, not tokens.

Tolerance: the program computes in bfloat16 with float32 accumulation.
Its logits sit on average 0.6-0.8% of the logits' spread from the
reference (at most 5.6%); the reference's own W8A8 forward sits 1.8-2.7%
on average.  The mean limit of 1.5% passes the first and fails the
second; the max limit of 10% catches a single wrong position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import model
from tiny import LLAMA, SC2, _program, BENCH
import json

CASES = {
    "llama": ("deepseek-7b-pp4", "deepseek-7b", LLAMA),
    "starcoder2": ("starcoder2-3b", "starcoder2-3b", SC2),
}
L, PS = 40, 8


def _setup(case):
    from repro.configs import get_config
    base, arch, widths = CASES[case]
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(widths)
    a = model.arch_of(cfg)
    p = dict(cfg["program"]["replace"], **_program(arch, widths)["replace"])
    pcfg = get_config(arch).replace(**p)
    w = model.make_weights(a, 1234)
    toks = np.random.default_rng(0).integers(0, a.vocab, L + 1).astype(
        np.int32)
    return a, pcfg, w, toks


def program_logits(pcfg, params, toks):
    """Logits of every prompt position (one prefill step), then of one
    decode step through the cache the prefill wrote."""
    from repro.models import build_model
    m = build_model(pcfg)
    pages = -(-(L + 1) // PS)
    pool = m.init_cache(pages + 1, PS)              # last page: scratch
    table = np.arange(pages, dtype=np.int32)
    pre, pool = m.step_ragged(
        params, jnp.asarray(toks[:L]), pool, jnp.asarray(np.tile(table, (L, 1))),
        jnp.arange(L, dtype=jnp.int32), jnp.arange(L, dtype=jnp.int32),
        cu_seqlens=jnp.asarray([0, L], jnp.int32))
    dec, _ = m.step_ragged(
        params, jnp.asarray(toks[L:]), pool, jnp.asarray(table[None]),
        jnp.asarray([L], jnp.int32), jnp.asarray([0], jnp.int32),
        cu_seqlens=jnp.asarray([0, 1], jnp.int32))
    return np.concatenate([np.asarray(pre), np.asarray(dec)], 0)


def ref_logits(a, w, toks, lowp=False):
    with jax.default_matmul_precision("highest"):
        h = model.hidden(a, w, jnp.asarray(toks), lowp, block=L + 1)
        return np.asarray(model.logits(a, w, h, lowp))


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_program_logits(case):
    a, pcfg, w, toks = _setup(case)
    got = program_logits(pcfg, model.program_params(a, w), toks)
    want = ref_logits(a, w, toks)
    spread = want.std()
    err = np.abs(got - want) / spread
    assert err.mean() < 0.015 and err.max() < 0.1, (err.mean(), err.max())
    ctl = np.abs(ref_logits(a, w, toks, lowp=True) - want) / spread
    assert ctl.mean() > 0.015, ctl.mean()


@pytest.mark.parametrize("case", sorted(CASES))
def test_score_gap_of_served_tokens(case):
    """``served_scores`` reads a gap of 0 for tokens the reference itself
    picks, and above them no mass."""
    a, pcfg, w, toks = _setup(case)
    prompt = toks[:8]
    served = []
    for _ in range(6):
        seq = np.concatenate([prompt, np.asarray(served, np.int32)])
        served.append(int(ref_logits(a, w, seq)[-1].argmax()))
    gaps, above = model.served_scores(a, w, prompt, served, 0.7, 64)
    assert gaps.shape == (6,) and np.all(gaps == 0.0)
    assert np.all(above == 0.0)
    wrong = [(t + 1) % a.vocab for t in served]
    gaps, _ = model.served_scores(a, w, prompt, wrong, 0.7, 64)
    assert np.all(gaps > 0.0)


@pytest.mark.parametrize("temperature", [0.2, 1.0])
def test_score_mass_above_served_token(temperature):
    """``above`` is the softmax mass, at the request's temperature, of the
    tokens ranked above the served one, by hand from the logits."""
    a, pcfg, w, toks = _setup(sorted(CASES)[0])
    prompt = toks[:8]
    lg = ref_logits(a, w, prompt)[-1].astype(np.float64)
    order = np.argsort(-lg)
    p = np.exp((lg - lg.max()) / temperature)
    p /= p.sum()
    for rank in (0, 1, 5, 40):
        tok = int(order[rank])
        _, above = model.served_scores(a, w, prompt, [tok], temperature, 64)
        assert abs(above[0] - p[order[:rank]].sum()) < 1e-4, rank
