#!/usr/bin/env bash
# Tier-1 CI: full test suite, then the tracked benchmarks.
#
#   ./scripts/ci.sh            # everything
#   SKIP_BENCH=1 ./scripts/ci.sh   # tests only
#
# BENCH_planner.json / BENCH_search.json are the committed planner
# trajectories — regenerate them here so planner and search regressions
# show up in review diffs. Serving speed is measured on the chip by
# bench/run.py, not here.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# no bytecode in the tree: 8 .pyc files were accidentally committed once.
# Flags tracked bytecode anywhere AND any tracked file inside a
# __pycache__ dir; a deliberate __pycache__/.gitkeep placeholder is the
# one ignored exception (the dir entry itself is not an artifact leak).
if git ls-files | grep -E '\.py[co]$|(^|/)__pycache__/' \
        | grep -vE '(^|/)__pycache__/\.gitkeep$' | grep -q .; then
    echo "ERROR: tracked bytecode artifacts:" >&2
    git ls-files | grep -E '\.py[co]$|(^|/)__pycache__/' \
        | grep -vE '(^|/)__pycache__/\.gitkeep$' >&2
    exit 1
fi

# static lint/typecheck (repo tooling; gated so CI also runs on images
# that bake only the runtime deps — requirements-dev.txt lists both)
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks scripts
else
    echo "ruff not installed; skipping lint (pip install -r requirements-dev.txt)"
fi
if command -v mypy >/dev/null 2>&1; then
    mypy --ignore-missing-imports src/repro/core src/repro/analysis
else
    echo "mypy not installed; skipping typecheck (pip install -r requirements-dev.txt)"
fi

python -m pytest -q

# the legacy-wrapper shims must stay warning-clean at import time: only
# USING a deprecated kwarg / loading a v1 bundle may warn, importing the
# public modules may not
python -W error::DeprecationWarning - <<'PY'
import repro.analysis
import repro.analysis.bundle_lint
import repro.analysis.counters
import repro.analysis.decode_lint
import repro.analysis.lint
import repro.analysis.soundness
import repro.core
import repro.core.artifact
import repro.core.planner
import repro.core.unified
import repro.launch.compile
import repro.launch.dryrun
import repro.launch.serve
import repro.runtime.arena
import repro.runtime.engine
import repro.runtime.executor
import repro.runtime.residency
print("import smoke: no DeprecationWarning on import")
PY

# python -O smoke: under -O assert statements are stripped, so every
# checker must raise explicitly. Imports must work, and the core/validate
# oracle + the analysis certifier must both still flag a corrupt plan.
python -O - <<'PY'
import repro.analysis.lint
import repro.launch.compile
import repro.runtime.engine
from repro.analysis import soundness
from repro.core import validate
from repro.core.records import make_records

recs = make_records([(0, 2, 64), (1, 3, 64)])  # overlap in time...
offsets = {0: 0, 1: 0}                         # ...and in memory
try:
    validate.check_offsets(
        recs, type("A", (), {"strategy": "x", "offsets": offsets,
                             "total_size": 64})()
    )
except validate.PlanValidationError:
    pass
else:
    raise SystemExit("python -O silently disabled core/validate!")
findings = soundness.certify_offsets(recs, offsets, 64)
assert_ok = [f for f in findings if f.code == "arena-collision"]
if not assert_ok:
    raise SystemExit("python -O: certifier missed the collision!")
print("python -O smoke: checkers still raise with asserts stripped")
PY

# compile→artifact→serve round trip on a fleet sweep: compile.py --all
# over two small archs into ONE temp manifest (through the default-on
# pre-publish lint gate), then:
#   * repro.analysis.lint bundles over the manifest must come back with
#     ZERO findings (--strict: warnings fail too) — the committed
#     zero-findings baseline;
#   * the compiled decode step + scan block for both archs must pass the
#     static decode lint: donation aliased, zero host transfers;
#   * serve.py bucket auto-selection picks the nearest compiled bucket
#     for a max_len with no exact match — with zero jaxpr traces, zero
#     planner calls, zero cross-step state layouts, and zero XLA
#     compiles through every served token (plans AND AOT decode
#     executables ship in the v3 bundle).
# State residency: the served engine's LIVE device state bytes must equal
# the bundled StatePlan.total_size exactly (one plan-backed allocation),
# and a REPRO_STATE_RESIDENCY=off rerun must emit identical tokens (the
# residency-on/off differential decode check).
python - <<'PY'
import os
import tempfile
from repro.analysis import counters
from repro.analysis.lint import main as lint_main
from repro.launch import serve
from repro.launch.compile import main as compile_main
import sys

with tempfile.TemporaryDirectory() as d:
    sys.argv = ["compile", "--all", "--archs", "qwen3-0.6b", "mamba2-2.7b",
                "--slots-list", "2", "--max-lens", "32", "64", "--out", d]
    compile_main()
    rc = lint_main(["--strict", "bundles", d])
    assert rc == 0, f"bundle lint over the CI sweep manifest failed ({rc})"
    rc = lint_main(["decode", "qwen3-0.6b", "mamba2-2.7b",
                    "--slots", "2", "--max-len", "32", "--block", "4"])
    assert rc == 0, f"compiled-decode lint failed ({rc})"
    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls", "compile_calls"
    ) as cap:
        argv = [
            "--arch", "qwen3-0.6b", "--requests", "2", "--prompt-len", "3",
            "--max-new", "2", "--slots", "2", "--max-len", "48",
            "--plan-bundle", d,
        ]
        stats = serve.run(argv)
    assert stats["plan_source"] == "bundle", stats["bundle_warning"]
    assert stats["requested_max_len"] == 48 and stats["effective_max_len"] == 64, stats
    assert cap.delta("trace_calls") == 0, "auto-selected bundle traced a jaxpr"
    assert cap.delta("plan_calls") == 0, "auto-selected bundle invoked the planner"
    assert cap.delta("state_plan_calls") == 0, "auto-selected bundle laid out state"
    assert stats["aot_warning"] is None, stats["aot_warning"]
    assert stats["aot_executables"], "v3 bundle served without AOT executables"
    assert cap.delta("compile_calls") == 0, (
        "v3 bundle paid an XLA compile — zero-compile cold start broken"
    )
    assert stats["tokens"] == 4
    # one state allocation: live device state bytes == StatePlan.total_size
    assert stats["state_residency"] is True, stats
    assert stats["state_live_bytes"] == stats["state_planned_bytes"], stats
    # residency-on/off differential: the XLA-allocated baseline must emit
    # the exact same tokens
    os.environ["REPRO_STATE_RESIDENCY"] = "off"
    try:
        baseline = serve.run(argv)
    finally:
        del os.environ["REPRO_STATE_RESIDENCY"]
    assert baseline["state_residency"] is False, baseline
    assert baseline["tokens_per_request"] == stats["tokens_per_request"], (
        "residency-on tokens diverged from the XLA-allocated baseline"
    )
print("compile --all → lint → serve: zero-findings manifest, decode lint "
      "clean (donation aliased, no host transfers), nearest-bucket "
      "auto-selection with zero traces/plans/state layouts, live state == "
      "planned, residency differential clean")
PY

# scan-block serving: --block-size K must sync with the host EXACTLY once
# per scan block (the host_syncs counter — same discipline as the
# zero-trace/zero-plan asserts) and emit tokens byte-identical to the
# single-wave host loop.
python - <<'PY'
from repro.launch import serve

host = serve.run(["--requests", "3", "--prompt-len", "4", "--max-new", "6",
                  "--slots", "2", "--max-len", "64"])
block = serve.run(["--requests", "3", "--prompt-len", "4", "--max-new", "6",
                   "--slots", "2", "--max-len", "64", "--block-size", "4"])
assert block["host_syncs"] == block["blocks"], (
    f"{block['host_syncs']} host syncs over {block['blocks']} scan blocks — "
    f"the block path must sync exactly once per block"
)
assert block["host_syncs"] < host["host_syncs"], (host, block)
assert block["tokens_per_request"] == host["tokens_per_request"], (
    "greedy scan-block tokens diverged from the host loop"
)
assert block["slot_log"] == host["slot_log"]
print(f"scan-block serve: {block['host_syncs']} syncs over "
      f"{block['blocks']} blocks (host loop: {host['host_syncs']}), "
      f"greedy tokens + slot log identical")
PY

# paged state serving: a --page-size bucket compiles through the same
# pre-publish gate into a v3 manifest whose --strict lint baseline now
# covers the paged-* soundness codes + paged-meta-mismatch, serves with
# zero traces / plans / state layouts / XLA compiles, emits tokens
# identical to the symmetric host loop, and reports honest page
# economics: live state bytes == pages_live x page_size, peak pool
# usage strictly under the symmetric plan's constant footprint.
python - <<'PY'
import sys
import tempfile
from repro.analysis import counters
from repro.analysis.lint import main as lint_main
from repro.launch import serve
from repro.launch.compile import main as compile_main

with tempfile.TemporaryDirectory() as d:
    sys.argv = ["compile", "--arch", "qwen3-0.6b", "--slots", "2",
                "--max-len", "64", "--page-size", "1024", "--out", d]
    compile_main()
    rc = lint_main(["--strict", "bundles", d])
    assert rc == 0, f"paged bundle failed the --strict lint baseline ({rc})"
    argv = ["--arch", "qwen3-0.6b", "--requests", "3", "--prompt-len", "4",
            "--max-new", "4", "--slots", "2", "--max-len", "64"]
    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls", "compile_calls"
    ) as cap:
        paged = serve.run(argv + ["--page-size", "1024",
                                  "--plan-bundle", d])
    assert paged["plan_source"] == "bundle", paged["bundle_warning"]
    for c in ("trace_calls", "plan_calls", "state_plan_calls",
              "compile_calls"):
        assert cap.delta(c) == 0, f"paged bundle serve paid {c}"
    assert paged["page_size"] == 1024, paged
    # report honesty: live bytes ARE pages_live x page_size (drained
    # engine: both zero), and the peak never exceeded the pool
    assert paged["state_live_bytes"] == paged["state_pages_live"] * 1024, paged
    peak = paged["state_pages_live_peak"]
    assert 0 < peak <= paged["state_pages_total"], paged
    # the paged win: peak pool bytes strictly under the symmetric plan's
    # constant n_slots x slot_stride footprint at this fill
    assert peak * 1024 < paged["state_planned_bytes"], (
        f"paged peak {peak * 1024} B >= symmetric {paged['state_planned_bytes']} B"
    )
    assert paged["page_log"], "paged serve logged no page residencies"
    # byte-identity headline: same tokens as the symmetric host loop
    sym = serve.run(argv)
    assert paged["tokens_per_request"] == sym["tokens_per_request"], (
        "paged tokens diverged from the symmetric baseline"
    )
print(f"paged serve: --strict lint clean, zero traces/plans/compiles, "
      f"live bytes == pages_live x page_size, peak "
      f"{peak} pages < symmetric footprint, tokens identical")
PY

# planner scaling smoke: the full portfolio legs must plan a 50k-record
# graph inside a hard wall-clock ceiling (the pre-heap greedy-by-size
# -improved took ~67 s here; the pre-vectorized arena minutes), and the
# resulting plan must pass the soundness certifier — fast AND sound, not
# fast instead of sound.
python - <<'PY'
import sys
import time
sys.path.insert(0, "benchmarks")
from planner_scaling import synth_records
from repro.analysis import soundness
from repro.core.planner import plan_records

recs = synth_records(50_000)
t0 = time.perf_counter()
plan = plan_records(recs, mode="offsets", strategy="greedy_by_size",
                    graph_name="ci-smoke-50k")
improved = plan_records(recs, mode="shared_objects",
                        strategy="greedy_by_size_improved",
                        graph_name="ci-smoke-50k")
wall = time.perf_counter() - t0
CEILING_S = 30.0
assert wall < CEILING_S, (
    f"50k-record planning took {wall:.1f}s >= {CEILING_S}s ceiling — "
    "a fast path regressed to quadratic"
)
for p in (plan, improved):
    errors = [f for f in soundness.certify_plan(p) if f.severity == "error"]
    assert not errors, f"{p.strategy}: {[f.message for f in errors]}"
print(f"planner smoke: 50k records planned in {wall:.1f}s "
      f"(< {CEILING_S:.0f}s ceiling), offsets {plan.total_size} B / "
      f"shared-objects {improved.total_size} B, both certified sound")
PY

# prefill+decode round trip: a --prefill-len bucket compiles through the
# same pre-publish gate into a v4 bundle that carries a PLANNED prefill
# activation arena (certified by the --strict lint baseline alongside the
# decode plan), keys its bucket with the |pfN suffix, and still serves
# decode requests with zero traces / plans / state layouts / XLA
# compiles — prefill metadata is inert extra planning, never a serving
# cost.
python - <<'PY'
import json
import pathlib
import sys
import tempfile
from repro.analysis import counters
from repro.analysis.lint import main as lint_main
from repro.core import artifact
from repro.launch import serve
from repro.launch.compile import main as compile_main

with tempfile.TemporaryDirectory() as d:
    sys.argv = ["compile", "--arch", "qwen3-0.6b", "--slots", "2",
                "--max-len", "64", "--prefill-len", "32", "--out", d]
    compile_main()
    rc = lint_main(["--strict", "bundles", d])
    assert rc == 0, f"prefill bundle failed the --strict lint baseline ({rc})"
    manifest = json.loads((pathlib.Path(d) / "manifest.json").read_text())
    keys = list(manifest["buckets"])
    assert any(k.endswith("|pf32") for k in keys), keys
    bundle = artifact.load_bundle(
        pathlib.Path(d) / manifest["buckets"][keys[0]]["file"])
    assert bundle.prefill_len == 32 and bundle.prefill_plan is not None, (
        bundle.summary()
    )
    assert bundle.peak_activation_size >= bundle.plan.total_size
    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls", "compile_calls"
    ) as cap:
        stats = serve.run(["--arch", "qwen3-0.6b", "--requests", "2",
                           "--prompt-len", "3", "--max-new", "2",
                           "--slots", "2", "--max-len", "64",
                           "--plan-bundle", d])
    assert stats["plan_source"] == "bundle", stats["bundle_warning"]
    for c in ("trace_calls", "plan_calls", "state_plan_calls",
              "compile_calls"):
        assert cap.delta(c) == 0, f"prefill bundle serve paid {c}"
    assert stats["tokens"] == 4
print("prefill round trip: --prefill-len 32 bundle lints clean (strict), "
      "bucket keyed |pf32, planned prefill arena on board, decode serve "
      "zero traces/plans/state layouts/compiles")
PY

if [[ -z "${SKIP_BENCH:-}" ]]; then
    python benchmarks/planner_scaling.py --quick --out BENCH_planner.json
    # order/fusion search smoke: asserts footprint <= baseline on every
    # config and strictly smaller on >= 3 (BENCH_search.json is the
    # committed trajectory)
    python benchmarks/order_search_bench.py --quick --out BENCH_search.json
fi
