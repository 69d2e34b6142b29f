"""pad_share (%): rows the steps computed beyond the live tokens, as a share
of all rows computed, over the window (registry counters
``padded_rows_total``, which sums each step's stream width, and
``live_rows_total``)."""


def read(run):
    width = run.reg.get("padded_rows_total", 0)
    live = run.reg.get("live_rows_total", 0)
    if not width:
        return None
    return 100.0 * (width - live) / width
