"""The device allocator's peak bytes in use, read right after the window
and before any reference work, in GiB."""


def read(ctx):
    return ctx.peak_bytes / 2**30
