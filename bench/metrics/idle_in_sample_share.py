"""Engine sampling: the share of the traced part of the window in which
no operation ran on the device while the program's ``repro.sample``
span was open on the host, in percent."""

from harness import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.named("repro.sample"):
        return None
    return 100.0 * s.idle_within("repro.sample") / s.window_s
