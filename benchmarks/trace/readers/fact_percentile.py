"""A percentile of a list the harness clocked: ``ctx.facts[key]``."""

import numpy as np


def read(ctx, key: str, q: float):
    values = ctx.facts.get(key)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
