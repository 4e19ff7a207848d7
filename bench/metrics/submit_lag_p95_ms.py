"""Load generator: 95th percentile of how late a request due in the
window was submitted (submit time minus due time), in milliseconds. The
generator shares its thread with the engine calls, so a request that
falls due during a call waits for the call to return."""

from harness.measures import percentile


def read(ctx):
    p = percentile([rec.submit - rec.due for rec in ctx.due_in_window()], 95)
    return None if p is None else 1e3 * p
