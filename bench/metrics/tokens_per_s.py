"""Output tokens emitted in the window over the window's length: the
tokens of the calls that returned by the window's end, over the time
from its start to its end. A call still running at the end counts with
its time and without its tokens, whatever its length."""


def read(ctx):
    r = ctx.run
    return (sum(c.emitted for c in r.calls if c.end <= r.t_end)
            / (r.t_end - r.t0))
