"""Serving benchmark: one cell of BENCHMARK.json on the chip it runs on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process makes the weights from the seed on the device, builds the
engine as ``launch/serve.py`` builds it (default state backend and
decode loop), warms every shape the cell's traffic uses, then drives
the engine with that traffic for ``--seconds`` on the host clock. After
the window it reads the device's peak memory, frees the engine's state
and compares a seeded sample of the served requests with the plain
float32 reference (``bench/harness/correctness.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics from a profiler trace of
the window), ``device`` and, last, ``checks``: each number compared with
its limit. The checks are also the last lines of standard error.

It exits 2, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and 1 where the program cannot be found. It sets
no TPU flags: ``LIBTPU_INIT_ARGS`` stays as the machine sets it.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from harness import spec  # noqa: E402

OUT = ROOT / ".bench_out"  # traces, reduced and deleted after each run
DECODE_PROGRAM = "decode_step"  # the served decode jit's function name
# a traced run profiles the last seconds of its window: the slots are
# full by then, and a trace of the whole window is too large to read
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    pass


def configure_jax(root: Path = ROOT):
    """Persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR`` or a
    fixed directory in the checkout, keeping every compile (minimum
    compile time 0), so only a cell's first run in a checkout compiles."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def find_chips(jax, n: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


class CompileMeter:
    """Backend compiles (cache reads included) from JAX's monitoring
    events, so a compile inside the window shows whatever code paid it."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


@dataclasses.dataclass
class Served:
    """One run of a cell up to the close of its window."""

    cell: spec.Cell
    params: object
    run: object  # harness.client.Run
    setup_s: float
    peak_bytes: int
    planned_state_bytes: int | None
    window_compiles: int
    xplane: Path | None


def program_config(config: dict):
    """The program's ArchConfig for a configuration file: the repo's arch
    with the file's overrides to published sizes."""
    from repro.configs.base import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    if cfg.dtype != config["deployment"]["dtype"]:
        raise ValueError(f"program serves {cfg.dtype}, deployment states "
                         f"{config['deployment']['dtype']}")
    return cfg


def serve(cell: spec.Cell, seed: int, seconds: float, trace: bool,
          meter: CompileMeter, setup_start: float, device) -> Served:
    """Weights, engine, warm-up, window; the engine is freed on return."""
    import jax

    from harness import client as load
    from harness import traffic, weights
    from repro.analysis import counters
    from repro.models.api import Model
    from repro.runtime.engine import InferenceEngine

    config, dep = cell.config, cell.config["deployment"]
    cfg = program_config(config)
    model = Model.for_config(cfg)
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), seed)
    jax.block_until_ready(params)
    stream = traffic.generate(cell.mix, seed, vocab=config["vocab_size"],
                              n_slots=dep["n_slots"], seconds=seconds,
                              max_len=dep["max_len"])
    # the arguments launch/serve.py run() passes, at its defaults
    engine = InferenceEngine(
        cfg, params, n_slots=dep["n_slots"], max_len=dep["max_len"],
        session=None, greedy=True, sample_seed=0, temperature=1.0, top_k=0,
        eos_id=None, block_size=dep["block_size"], page_size=None,
        page_pool=None,
    )
    client = load.Client(engine, stream, spans=trace)
    client.setup()
    xplane = None
    start_trace = None
    if trace:
        import jax.profiler

        shutil.rmtree(OUT / "trace", ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1

        def start_trace():
            jax.profiler.start_trace(str(OUT / "trace"), profiler_options=opts)
    gc.collect()
    compiles0 = meter.compiles
    counted0 = counters.read("compile_calls")
    setup_s = time.perf_counter() - setup_start
    # the garbage collector stays on in the window, as in launch/serve.py
    run = client.window(seconds, trace_start=start_trace,
                        trace_seconds=TRACE_SECONDS)
    window_compiles = (meter.compiles - compiles0
                       + counters.read("compile_calls") - counted0)
    if trace:
        jax.profiler.stop_trace()
        from harness.trace import find_xplane

        xplane = find_xplane(OUT / "trace")
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    planned = engine.memory_report.state_planned_bytes
    for rec in run.records:  # keep the tokens, drop the engine's objects
        rec.tokens, rec.req = list(rec.req.tokens), None
    del engine, client
    gc.collect()
    return Served(cell, params, run, setup_s, peak, planned,
                  window_compiles, xplane)


def served_pairs(served: Served) -> list:
    """(prompt, tokens) of every request the run finished — or, where
    none finished, of every request that was served a token."""
    records = served.run.records
    done = [r for r in records if r.done is not None and r.tokens]
    return [(r.planned.prompt, r.tokens) for r in done or
            [r for r in records if r.tokens]]


def result(served: Served, seed: int, trace: bool, device_info: dict,
           control: bool = False) -> dict:
    """Correctness, metrics and the result line of a served run. With
    ``control`` the float8 reference stands in the program's place: the
    widest gap compared is the control's, at the same positions of the
    same served requests, and the program's own goes to ``detail``."""
    from harness import correctness, trace as tr
    from harness.measures import Context

    cell = served.cell
    config = cell.config
    rows, T = correctness.shape_for(cell.mix, config["deployment"]["max_len"])
    pairs = served_pairs(served)
    sample = correctness.draw(pairs, seed, rows, T) if pairs else None
    gap, cgap = (correctness.widest_gaps(
        spec.reference_module(cell.root, config), config, served.params,
        sample, control=control) if sample else (float("inf"), float("nan")))
    limit = config["correct"]["max_logit_gap"]
    program_gap = gap
    if control:
        gap = cgap
    records = served.run.records
    failed = [r for r in records if r.done is not None
              and len(r.tokens) != r.planned.max_new]
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "window_compiles": {"value": served.window_compiles, "limit": 0},
        "failed_requests": {"value": len(failed), "limit": 0},
    }
    correct = (gap <= limit and served.window_compiles == 0 and not failed)
    summary = None
    if trace:
        summary = tr.reduce(tr.load(served.xplane), program=DECODE_PROGRAM)
    ctx = Context(served.run, spec.dims(cell.root, config), device_info["peaks"],
                  served.setup_s, served.peak_bytes,
                  served.planned_state_bytes, summary)
    metrics = {}
    for m in cell.metrics(trace):
        value = spec.metric_reader(cell.root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {k: v for k, v in device_info.items() if k != "peaks"}
    device["memory_peak_bytes"] = served.peak_bytes
    line = {"correct": bool(correct), "attempted": len(records),
            "failed": len(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["detail"] = {"served_compared": sample.served if sample else 0,
                      "requests_finished": sum(r.done is not None
                                               for r in records),
                      "decode_calls": len(served.run.calls)}
    if control:
        line["detail"]["program_max_logit_gap"] = program_gap
    line["checks"] = checks
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload, ROOT)
        jax = configure_jax(ROOT)
        devices = find_chips(jax, cell.chips)
        import repro  # noqa: F401  (the program under test)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except (spec.SpecError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    meter = CompileMeter()
    dev = devices[0]
    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "peaks": spec.peaks(ROOT, dev.device_kind)}
    served = serve(cell, args.seed, args.seconds, bool(args.trace), meter,
                   PROCESS_START, dev)
    line = result(served, args.seed, bool(args.trace), device_info)
    if served.xplane is not None:
        shutil.rmtree(OUT / "trace", ignore_errors=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
