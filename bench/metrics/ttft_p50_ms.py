"""Median, over the requests due in the window, of the time from when a
request was due to its first token, in milliseconds. A request with no
first token by the window's close counts at its wait so far."""

import numpy as np


def read(ctx):
    close = ctx.run.t_close
    waits = [(rec.token_times[0] if rec.token_times else close) - rec.due
             for rec in ctx.due_in_window()]
    return 1e3 * float(np.median(waits)) if waits else None
