"""Engine sampling: median host time of the program's ``repro.sample``
span (the per-slot logits row fetch, argmax and retirement after a
decode wave) in the traced part of the window, in milliseconds."""

import numpy as np

from harness import spans


def read(ctx):
    s = spans.of(ctx)
    d = s.durations("repro.sample") if s else []
    return 1e3 * float(np.median(d)) if d else None
