"""Load generator (the benchmark's own driver): 95th percentile over the
window's requests of how late each was submitted after it was due. A
starved generator shows here, and not as a fast server. Requests that were
waiting while the profiler started or stopped (``ctx.stalls``, which only a
traced run has) are left out: that stall is the measurement's, not the
generator's."""

import numpy as np

NAME, UNIT = "gen_lag_p95_ms", "ms"


def read(ctx):
    lag = [(r.submit - r.due) * 1e3 for r in ctx.recs
           if r.submit is not None
           and not any(r.due < b and r.submit > a for a, b in ctx.stalls)]
    return float(np.percentile(lag, 95)) if lag else None
