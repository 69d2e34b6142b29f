"""attn_kernel_roofline.<cell kind> (%): the varlen paged attention
kernel's least time over its device time in the traced window.

Least time per step is the larger of its operations over the bf16 peak and
its bytes over the HBM bandwidth (benchlib.workcount: what attention needs
for the step's live tokens, whatever implements it).  Device time is the
sum of the kernel's ops (names holding ``KERNEL``) inside the window's
engine steps.  The steps counted on both sides are those wholly inside the
window."""
from benchlib import workcount

KERNEL = "paged_attention"


def read(run):
    t = run.trace
    if run.peaks is None or t.window is None or not t.ops:
        return None
    lo, hi = t.window
    spans = [(a, b) for a, b in t.host.get("engine.step", ())
             if lo <= a and b <= hi]
    kernel = sum((b - a) * 1e-9 for n, a, b in t.ops[0]
                 if KERNEL in n and any(s <= a < e for s, e in spans))
    steps = [s for s in run.steps if s.pos is not None and len(s.pos)]
    if not kernel or not steps or len(run.steps) != len(spans):
        return None
    quant = bool(getattr(run.pcfg, "kv_quant", False))
    least = 0.0
    for s in steps:
        flops = workcount.attention_flops(run.arch, s.pos)
        nbytes = workcount.attention_bytes(
            run.arch, s.pos, s.cu, 1 if quant else 2, 2, 4 if quant else 0)
        least += workcount.least_seconds(flops, nbytes,
                                         run.peaks["bf16_flops"],
                                         run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel
