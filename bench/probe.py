"""Readings for setting a cell up, in one process on the chip: each seed
serves one window of the cell's timed path, then prints one JSON line
with the result line of that run and, for an open-loop mix, how the
time to first token moved through the window. Not part of a benchmark
run.

    python bench/probe.py --workload <cell> --seeds 1,2,3 --seconds 30 \\
        [--control] [--rates R1,R2] [--metrics a,b] [--out probe.jsonl]

``--workload`` names a cell of ``BENCHMARK.json``, or ``<config>.<mix>``
for a pair that is not a cell yet. ``--control`` puts the float8
reference in the program's place, so the line's ``correct`` is the
control's (``detail.program_max_logit_gap`` keeps the program's).
``--rates`` replaces an open-loop mix's ``rate_per_s`` by each rate in
turn, for the sweep that finds the highest rate the system sustains.
``--metrics`` names readers in ``bench/metrics/`` to report besides
the cell's own.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

import run as bench


def cell_for(workload: str):
    try:
        return bench.spec.load_cell(workload, bench.ROOT)
    except bench.spec.SpecError:
        config, _, mix = workload.rpartition(".")
        return bench.spec.make_cell(workload, config, mix, 1, bench.ROOT)


def open_loop(served) -> dict:
    """Requests due in the window, how many had a first token by its
    close, and the median time to first token of each third of them by
    due time: a rate the system sustains keeps the thirds alike."""
    run = served.run
    due = sorted((r for r in run.records
                  if not r.in_setup and run.t0 <= r.due < run.t_end),
                 key=lambda r: r.due)
    ttft = [(r.token_times[0] if r.token_times else run.t_close) - r.due
            for r in due]
    thirds = [1e3 * float(np.median(part)) for part in
              np.array_split(np.asarray(ttft), 3) if len(part)]
    return {"due": len(due), "first_token_by_close":
            sum(bool(r.token_times) for r in due),
            "ttft_p50_ms_by_third": thirds}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--metrics", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    base = cell_for(args.workload)
    extra = tuple({"name": n, "unit": ""} for n in args.metrics.split(",") if n)
    base = dataclasses.replace(base, end_to_end=base.end_to_end + extra)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    jax = bench.configure_jax(bench.ROOT)
    dev = bench.find_chips(jax, base.chips)[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": 1,
            "peaks": bench.spec.peaks(bench.ROOT, dev.device_kind)}
    meter = bench.CompileMeter()
    out = open(args.out, "a") if args.out else None
    try:
        for rate, seed in ((r, int(s)) for r in rates
                           for s in args.seeds.split(",")):
            cell = base
            if rate is not None:
                cell = dataclasses.replace(base, mix=dict(
                    base.mix, arrivals=dict(base.mix["arrivals"],
                                            rate_per_s=rate)))
            t = time.perf_counter()
            served = bench.serve(cell, seed, args.seconds, False, meter, t, dev)
            line = bench.result(served, seed, False, info, control=args.control)
            line = {"workload": args.workload, "seed": seed, "rate": rate,
                    "control": args.control,
                    "unfinished": sum(r.done is None for r in served.run.records),
                    **line}
            if cell.mix["arrivals"]["kind"] != "closed":
                line["open_loop"] = open_loop(served)
            del served
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
