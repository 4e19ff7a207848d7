"""Model step: the model operations of every token the window processed
(prompt tokens through the layers, output tokens through the head too,
each at its context length) over the window's length times the chip's
peak bf16 rate, in percent."""

from harness import counts


def read(ctx):
    flops = sum(counts.step_cost(ctx.dims, step)[0] for step in ctx.dispatches())
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
