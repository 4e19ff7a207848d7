"""Model step: median host time of the window's calls that admitted
nothing (one decode wave over the active slots), in milliseconds."""

import numpy as np


def read(ctx):
    pure = ctx.pure_decode_calls()
    return 1e3 * float(np.median([c.end - c.start for c in pure])) if pure else None
