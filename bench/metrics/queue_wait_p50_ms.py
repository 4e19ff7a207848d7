"""Engine admission: median time from a request's due time to the start
of the call in which the engine admitted it (set ``admitted_wave``), in
milliseconds; a request not admitted by the window's close counts at
its wait so far."""

import numpy as np


def read(ctx):
    calls, close = ctx.run.calls, ctx.run.t_close
    waits = [(calls[rec.admit_call].start if rec.admitted else close)
             - rec.due for rec in ctx.due_in_window()]
    return 1e3 * float(np.median(waits)) if waits else None
