"""Spreads of sets of runs, as a bound is set from them: for each metric
the interquartile distance over the median (``statistics.quantiles``
with ``n=4``), of every run and with the run farthest from the median
left out. Not part of a benchmark run.

    python bench/spread.py setA.jsonl setB.jsonl

Each file is one set: lines as ``bench/run.py`` prints its last line.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def main(paths: list[str]) -> int:
    sets = [[json.loads(line) for line in open(p) if line.startswith("{")]
            for p in paths]
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    for name in names:
        cols = []
        for rows in sets:
            v = [r["metrics"][name]["value"] for r in rows
                 if name in r["metrics"]]
            if len(v) < 3:
                continue
            cols.append(f"median {statistics.median(v):.6g} spread "
                        f"{100 * spread(v):.2f}% ({100 * spread(without_farthest(v)):.2f}%"
                        f" without the farthest) n={len(v)} "
                        f"[{min(v):.6g}, {max(v):.6g}]")
        print(f"{name:28s} " + " | ".join(cols))
    for path, rows in zip(paths, sets):
        print(path, "correct", [r["correct"] for r in rows], "checks",
              [{k: round(c["value"], 4) for k, c in r["checks"].items()}
               for r in rows])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
