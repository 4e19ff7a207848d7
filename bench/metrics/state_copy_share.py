"""State backend: the device time of operations under the name scopes
``state.unpack`` and ``state.pack`` (the state buffer's copies into the
cache pytree and back) inside the decode program's executions, over
the device time of those executions, in the traced part of the window,
in percent. None where no operation carries either scope."""

from harness import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.scoped_s or not s.program_s:
        return None
    return 100.0 * sum(s.scoped_s.values()) / s.program_s
