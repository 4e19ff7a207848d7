"""95th percentile of the gaps between consecutive output tokens of one
request, both emitted in the window, in milliseconds."""

from harness.measures import percentile


def read(ctx):
    p = percentile(ctx.token_gaps(), 95)
    return None if p is None else 1e3 * p
