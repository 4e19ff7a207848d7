"""Seconds from process start to the window's start: imports, weights,
engine construction, compiles (or cache reads), warm-up and slot fill."""


def read(ctx):
    return ctx.setup_s
