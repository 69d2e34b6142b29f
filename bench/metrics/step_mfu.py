"""step_mfu.<cell kind> (%): the model operations of the tokens that the
window's steps processed (benchlib.workcount.model_flops: live tokens,
one logits row per served token, attention's causal work), over the sum of
those steps' wall time (host clock) times the chip's bf16 peak."""
from benchlib import workcount


def read(run):
    steps = [s for s in run.steps if s.pos is not None and len(s.pos)]
    if run.peaks is None or not steps:
        return None
    flops = sum(workcount.model_flops(run.arch, s.pos, s.committed)
                for s in steps)
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops"])
