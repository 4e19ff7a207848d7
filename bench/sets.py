"""Sets of benchmark runs of one cell, as its bounds are measured: each
run is its own process of ``bench/run.py`` (the parent never touches
JAX, so each child has the chip), one line per run appended to
``<out>/<workload>.set<k>.jsonl``, then the spreads (``bench/spread.py``).
Not part of a benchmark run.

    python bench/sets.py --workload <cell> --seeds 131,132,133 \\
        --sets 2 --seconds 50 [--trace 1] --out chiprun_out/sets
"""

from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path

import spread

RUN = Path(__file__).resolve().parent / "run.py"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    files = []
    for k in range(1, args.sets + 1):
        path = out / f"{args.workload}.set{k}.jsonl"
        files.append(str(path))
        for seed in args.seeds.split(","):
            cmd = [sys.executable, str(RUN), "--workload", args.workload,
                   "--seed", seed, "--seconds", str(args.seconds),
                   "--trace", str(args.trace)]
            p = subprocess.run(cmd, capture_output=True, text=True)
            (out / f"{args.workload}.set{k}.{seed}.err").write_text(p.stderr)
            last = p.stdout.strip().splitlines()[-1:] or [""]
            print(f"set {k} seed {seed} rc={p.returncode} "
                  f"{last[0][:400]}", flush=True)
            if p.returncode == 0 and last[0].startswith("{"):
                with open(path, "a") as f:
                    f.write(last[0] + "\n")
    files = [f for f in files if Path(f).is_file()]
    return spread.main(files) if files else 1


if __name__ == "__main__":
    sys.exit(main())
