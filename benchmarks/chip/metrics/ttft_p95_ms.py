"""95th percentile of time to first token over all requests due in the
window, each timed from the time it was due; a request that never started
counts the time until the run stopped waiting for it."""

import numpy as np

NAME, UNIT = "ttft_p95_ms", "ms"


def read(ctx):
    if not ctx.recs:
        return None
    ttft = [((r.first if r.first is not None else ctx.end_wait) - r.due) * 1e3
            for r in ctx.recs]
    return float(np.percentile(ttft, 95))
