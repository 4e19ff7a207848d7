"""Engine prompt feed: host milliseconds per admitted prompt token — the
time of the calls that admitted a request, less one median pure-decode
call each (their own decode wave), over the prompt tokens they admitted."""

import numpy as np


def read(ctx):
    admitting, pure = ctx.admitting_calls(), ctx.pure_decode_calls()
    if not admitting or not pure:
        return None
    wave = float(np.median([c.end - c.start for c in pure]))
    extra = sum(c.end - c.start - wave for c in admitting)
    tokens = sum(rec.prompt_len for c in admitting for rec in c.admitted)
    return 1e3 * extra / tokens
