"""Decode program: the least time of the window's decode-step executions
— each the larger of its operations over the peak rate and its bytes
over HBM bandwidth (bench/harness/counts.py) — over their device time
in the traced part of the window, in percent. Read only where the trace
holds exactly one execution of the program per decode step that the
traced calls ran."""

import sys

from harness import counts


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.run.t_trace is None:
        return None
    steps = ctx.dispatches(since=ctx.run.t_trace)
    if not tr.program_runs or not steps:
        return None
    if tr.program_runs != len(steps):
        print(f"decode_step_roofline: trace holds {tr.program_runs} decode "
              f"executions, the calls ran {len(steps)}; not read",
              file=sys.stderr)
        return None
    least = 0.0
    for step in steps:
        flops, nbytes = counts.step_cost(ctx.dims, step)
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / tr.program_s
