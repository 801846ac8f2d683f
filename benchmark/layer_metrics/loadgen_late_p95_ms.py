"""How late the generator submitted against its schedule, 95th percentile:
a starved generator must not read as a fast server."""

import numpy as np


def read(ctx):
    late = ctx.get("late_ms")
    if late is None or len(late) == 0:
        return None
    return float(np.quantile(np.asarray(late), 0.95))
