"""The program's own instrumentation as the benchmark reads it: the
engine's host spans in a CPU trace loaded through the benchmark's
loader, each new reader on hand-made events and on a traced run on the
CPU, the dispatch counter against the rebuild of dispatches from the
calls, and the reductions on events and a trace recorded on a v5e."""

import json
import shutil
import time

import jax
import numpy as np
import pytest

import tiny
import run as bench
from harness import client, counts, spans, trace as tr
from harness.client import Run
from harness.measures import Context
from harness.spec import metric_reader
from repro.analysis import counters

E = spans.Event
DEV, HOST = "/device:TPU:0", "/host:CPU"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
INFO = {"platform": "cpu", "kind": "cpu", "count": 1, "peaks": PEAKS}
NEW = ("host_sample_ms", "idle_in_sample_share", "prompt_feed_ms_per_token",
       "state_copy_share")


def _read(name, ctx):
    return metric_reader(tiny.ROOT, name)(ctx)


def _ctx(run=None, **kw):
    run = run or Run([], [], 0.0, 10.0, 10.0)
    return Context(run, counts.Dims(*[1] * 10), PEAKS, 1.0, 0, None, **kw)


def _events():
    """A 10 s traced part: two engine calls, each a step with a wave
    (decode program 1 s) and host sampling after it; the first admits a
    request and feeds 4 prompt tokens. Ops under the state scopes take
    0.25 s of each wave."""
    up = "jit(decode_step)/state.unpack/jit(_unpack)/dynamic_slice:"
    pk = "jit(decode_step)/state.pack/jit(_pack)/dynamic_update_slice:"
    body = "jit(decode_step)/while/body/dot_general:"
    return [
        E("host", HOST, "bench.window", 0.0, 10.0),
        E("host", HOST, "repro.step", 0.1, 4.8, "", (("active", 1),)),
        E("host", HOST, "repro.admit", 0.15, 2.0, "", (("admitted", 1),)),
        E("host", HOST, "repro.prompt_feed", 0.2, 1.6, "",
          (("rid", 3), ("tokens", 4))),
        E("host", HOST, "repro.state.decode", 2.2, 1.2),
        E("host", HOST, "repro.state.wait", 2.3, 1.1),
        E("host", HOST, "repro.sample", 3.5, 1.0, "", (("rows", 2),)),
        E("host", HOST, "repro.step", 5.1, 4.8, "", (("active", 2),)),
        E("host", HOST, "repro.state.decode", 5.2, 2.0),
        E("host", HOST, "repro.state.wait", 5.3, 1.9),
        E("host", HOST, "repro.sample", 7.3, 2.0, "", (("rows", 2),)),
        # prompt feed: two executions of 0.5 s; a scoped op of the reset
        # program, outside any decode execution, is not counted
        E("module", DEV, "jit_decode_step(1)", 0.3, 0.5),
        E("op", DEV, "", 0.3, 0.5, body),
        E("module", DEV, "jit_decode_step(1)", 1.0, 0.5),
        E("op", DEV, "", 1.0, 0.5, body),
        E("op", DEV, "", 1.8, 0.1, "jit(reset_slots)/state.pack/copy:"),
        # waves: 1 s each, a quarter of it under the state scopes
        E("module", DEV, "jit_decode_step(1)", 2.3, 1.0),
        E("op", DEV, "", 2.3, 0.15, up),
        E("op", DEV, "", 2.45, 0.75, body),
        E("op", DEV, "", 3.2, 0.1, pk),
        E("module", DEV, "jit_decode_step(1)", 5.5, 1.0),
        E("op", DEV, "", 5.5, 0.15, up),
        E("op", DEV, "", 5.65, 0.75, body),
        E("op", DEV, "", 6.4, 0.1, pk),
        # a short op while the second sampling span is open
        E("op", DEV, "", 8.0, 0.5, ""),
    ]


def test_reduction_of_program_spans_by_hand(monkeypatch):
    s = spans.reduce(_events())
    assert s.window_s == pytest.approx(10.0)
    assert [e.name for e in s.host].count("repro.state.decode") == 2
    assert s.program_s == pytest.approx(3.0)
    assert s.scoped_s == {"state.unpack": pytest.approx(0.3),
                          "state.pack": pytest.approx(0.2)}
    # device idle while sampling: 3.5..4.5 all idle; 7.3..9.3 less 8.0..8.5
    assert s.idle_within("repro.sample") == pytest.approx(1.0 + 1.5)
    monkeypatch.setattr(spans, "of", lambda ctx: s)
    ctx = _ctx()
    assert _read("host_sample_ms", ctx) == pytest.approx(1500.0)
    assert _read("idle_in_sample_share", ctx) == pytest.approx(25.0)
    assert _read("prompt_feed_ms_per_token", ctx) == pytest.approx(400.0)
    assert _read("state_copy_share", ctx) == pytest.approx(100 * 0.5 / 3.0)


def test_readers_find_nothing_without_program_spans(monkeypatch):
    """A trace of a program without the spans or scopes, and an untraced
    run: every new reader returns None and none raises."""
    old = [e._replace(scope="") for e in _events()
           if not e.name.startswith("repro.")]
    s = spans.reduce(old)
    assert not s.host and not s.scoped_s and s.program_s == pytest.approx(3.0)
    monkeypatch.setattr(spans, "of", lambda ctx: s)
    for name in NEW:
        assert _read(name, _ctx()) is None, name
    monkeypatch.undo()
    for name in NEW:
        assert _read(name, _ctx()) is None, name  # untraced: ctx.trace None


def test_prompt_feed_without_an_admission_is_none(monkeypatch):
    s = spans.reduce([e for e in _events() if e.name != "repro.prompt_feed"],
                     program="decode_step")
    monkeypatch.setattr(spans, "of", lambda ctx: s)
    assert _read("prompt_feed_ms_per_token", _ctx()) is None
    assert _read("host_sample_ms", _ctx()) == pytest.approx(1500.0)


def test_traced_tiny_run_reports_the_program_span_metrics():
    """The whole traced path of ``bench/run.py`` on the CPU: the readers
    find the run's trace, and a trace left from another run is not
    read. No CPU op carries a scope, so ``state_copy_share`` is absent."""
    cell = tiny.cell("qwen3-0.6b", "decode-batch", "bfloat16")
    real = bench.spec.load_cell("qwen3-0.6b.decode-batch", tiny.ROOT)
    cell = bench.dataclasses.replace(cell, per_layer=real.per_layer)
    trace_s = bench.TRACE_SECONDS
    bench.TRACE_SECONDS = 2.0
    try:
        served = bench.serve(cell, 2**32 + 11, 2.5, True, bench.CompileMeter(),
                             time.perf_counter(), jax.devices()[0])
        line = bench.result(served, 2**32 + 11, True, INFO)
        metrics = line["metrics"]
        assert metrics["host_sample_ms"]["value"] > 0
        assert 0 <= metrics["idle_in_sample_share"]["value"] <= 100
        assert metrics["prompt_feed_ms_per_token"]["value"] > 0
        assert "state_copy_share" not in metrics
        ctx = _ctx()
        ctx.trace = tr.reduce(tr.load(served.xplane),
                                    program="decode_step")
        assert spans.of(ctx) is not None
        ctx.trace.window_s += 1.0  # another run's traced part
        assert spans.of(ctx) is None
    finally:
        bench.TRACE_SECONDS = trace_s
        shutil.rmtree(spans.TRACE_DIR, ignore_errors=True)


@pytest.mark.parametrize("mix", ["decode-batch", "chat-overload"])
def test_dispatch_counter_equals_the_rebuild_of_dispatches(mix, monkeypatch):
    """On a tiny engine run, the program's count of decode executions
    over the window equals the benchmark's rebuild of them from the
    calls' admissions and waves (``Context.dispatches``)."""
    window, seen = client.Client.window, {}

    def counted(self, *args, **kw):
        before = counters.read("decode_dispatches")
        run = window(self, *args, **kw)
        seen["n"] = counters.read("decode_dispatches") - before
        return run

    monkeypatch.setattr(client.Client, "window", counted)
    cell = tiny.cell("qwen3-0.6b", mix, "bfloat16")
    served = bench.serve(cell, 2**32 + 9, 2.0, False, bench.CompileMeter(),
                         time.perf_counter(), jax.devices()[0])
    rebuilt = _ctx(served.run).dispatches()
    assert seen["n"] > 0 and any(c.admitted for c in served.run.calls)
    assert seen["n"] == len(rebuilt)


def test_engine_spans_nest_in_a_recorded_cpu_trace(tmp_path):
    """One step that admits a 5-token prompt and runs a wave, traced on
    the CPU and loaded through the benchmark's loader: step ⊃ admit ⊃
    prompt feed ⊃ state decode ⊃ state wait, then sampling."""
    from repro.configs.base import get_reduced
    from repro.models.api import Model
    from repro.runtime.engine import InferenceEngine

    cfg = get_reduced("qwen3-0.6b")
    params = Model.for_config(cfg).init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32)
    rid = engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        engine.step()
    jax.profiler.stop_trace()
    events = spans.load(tr.find_xplane(tmp_path))
    host = {}
    for e in sorted((e for e in events if e.name.startswith("repro.")),
                    key=lambda e: e.start):
        host.setdefault(e.name, []).append(e)

    def inside(a, b):
        return b.start <= a.start and a.start + a.dur <= b.start + b.dur

    (step,), (admit,), (feed,) = (host["repro.step"], host["repro.admit"],
                                  host["repro.prompt_feed"])
    (sample,) = host["repro.sample"]
    decodes, waits = host["repro.state.decode"], host["repro.state.wait"]
    assert len(decodes) == len(waits) == 4 + 1
    assert inside(admit, step) and inside(feed, admit)
    assert all(inside(d, feed) for d in decodes[:4])
    assert all(inside(w, d) for w, d in zip(waits, decodes))
    wave = decodes[4]
    assert inside(wave, step) and wave.start >= admit.start + admit.dur
    assert inside(sample, step) and sample.start >= wave.start + wave.dur
    assert dict(feed.args) == {"rid": rid, "tokens": 4}
    assert dict(admit.args) == {"admitted": 1}
    assert dict(step.args) == {"active": 0}
    assert dict(sample.args) == {"rows": 1}
    (req,) = engine.unfinished_requests()
    assert req.submitted_s <= req.admitted_s
    s = spans.reduce(events)
    assert [e.name for e in s.host].count("repro.state.decode") == 5


def _recorded():
    raw = json.loads((tiny.BENCH / "tests" / "data" /
                      "v5e_program_spans.json").read_text())
    events = [E(k, plane, name, start, dur,
                raw["scopes"][sc] if sc >= 0 else "", tuple(map(tuple, args)))
              for k, plane, name, start, dur, sc, args in raw["events"]]
    return [E("host", HOST, "bench.window", 0.0, raw["window_s"])] + events


def test_recorded_v5e_wave_matches_a_brute_force_count():
    """A prompt-feed execution and the decode wave after it, recorded on
    a TPU v5 lite (qwen3-0.6b, 8 slots x 2048): the state copies' share
    of the decode program's device time, and the device idle time under
    host sampling, against direct counts."""
    events = _recorded()
    s = spans.reduce(events)
    runs = [e for e in events if e.kind == "module" and "decode_step" in e.name]
    assert len(runs) == 2
    assert s.program_s == pytest.approx(sum(e.dur for e in runs))
    copied = sum(e.dur for e in events if e.kind == "op"
                 and any(spans.in_scope(e.scope, n) for n in spans.STATE_SCOPES)
                 and any(r.start <= e.start < r.start + r.dur for r in runs))
    assert sum(s.scoped_s.values()) == pytest.approx(copied)
    assert set(s.scoped_s) == {"state.unpack", "state.pack"}
    assert 30 < 100 * copied / s.program_s < 60
    # the wave's sampling: device idle on a 100 ns grid
    (sample,) = s.named("repro.sample")
    n = int(round(s.window_s * 1e7))
    busy = np.zeros(n, bool)
    for e in events:
        if e.kind == "op":
            a = max(int(round(e.start * 1e7)), 0)
            busy[a:max(min(int(round((e.start + e.dur) * 1e7)), n), a)] = True
    a, b = (int(round(t * 1e7)) for t in (sample.start, sample.start + sample.dur))
    assert s.idle_within("repro.sample") == pytest.approx(
        (~busy[a:b]).sum() * 1e-7, abs=2e-6)
    assert s.idle_within("repro.sample") > 0.5 * sample.dur
    # spans wholly inside the window: the last prompt execution and the wave
    assert sorted(e.name for e in s.host) == [
        "repro.sample", "repro.state.decode", "repro.state.decode",
        "repro.state.wait", "repro.state.wait"]


def test_scopes_of_a_trace_recorded_on_a_v5e():
    """A jitted step ``x -> x + sum((2x) @ (2x))`` whose doubling and
    final add are scoped calls (``state.unpack``, ``state.pack``), traced
    on a TPU v5 lite: each op's scope is read from its event metadata.
    A fusion carries the scope of its root, so the doubling, fused into
    the product, shows under the product's path."""
    path = tiny.BENCH / "tests" / "data" / "v5e_scoped_step.xplane.pb"
    (plane,) = [v for v in spans.op_scopes(path).values() if v]
    assert sorted(plane.values()) == [
        "jit(step)/dot_general:", "jit(step)/state.pack/jit(<lambda>)/add:"]
    events = spans.load(path)
    ops = [e for e in events if e.kind == "op"]
    assert len(ops) == 4 and all(e.plane == "/device:TPU:0" for e in ops)
    assert sum(spans.in_scope(e.scope, "state.pack") for e in ops) == 2
    steps = [e for e in events if e.name == "repro.step"]
    assert [dict(e.args) for e in steps] == [{"active": 0}, {"active": 1}]
