"""95th percentile of all inter-token gaps delivered in the window in
the slot-state engine's cells, on the benchmark's clock as each engine
step returned its tokens."""

import numpy as np

NAME, UNIT = "itl_p95_ms.ssm", "ms"


def read(ctx):
    if ctx.kind != "ssm":
        return None
    gaps = [g for t, g in ctx.gaps if ctx.origin <= t < ctx.window_end]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95) * 1e3)
