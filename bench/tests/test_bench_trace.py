"""The trace reduction: busy and idle time, idle gaps labelled by the
host span they fell in, the heaviest operations and one program's
executions — on a hand-made list of events, and on a CPU trace recorded
here through the same loader."""

import jax
import jax.numpy as jnp
import pytest

import tiny  # noqa: F401
from harness import trace as tr

E = tr.Event


def _events():
    dev = "/device:TPU:0"
    return [
        E("host", "/host:CPU", "bench.window", 1.0, 10.0),
        E("host", "/host:CPU", "bench.engine_call", 1.0, 4.0),
        E("host", "/host:CPU", "bench.wait", 5.0, 3.0),
        E("host", "/host:CPU", "bench.engine_call", 8.0, 3.0),
        # ops: overlapping pair, one clipped at the window's start
        E("op", dev, "fusion.1", 0.5, 1.5),   # counts 1.0 .. 2.0
        E("op", dev, "fusion.2", 1.5, 1.0),   # 1.5 .. 2.5, overlaps
        E("op", dev, "copy.3", 3.0, 1.0),     # 3.0 .. 4.0
        E("op", dev, "fusion.1", 9.0, 1.0),   # 9.0 .. 10.0
        E("module", dev, "jit_decode_step(7)", 1.5, 2.5),
        E("module", dev, "jit_decode_step(7)", 9.0, 1.0),
        E("module", dev, "jit_reset_slots(3)", 3.0, 0.5),
        E("module", dev, "jit_decode_step(7)", 10.5, 1.0),  # outside
    ]


def test_reduction_by_hand():
    s = tr.reduce(_events(), program="decode_step")
    assert s.window_s == pytest.approx(10.0)
    # busy: 1.0..2.5, 3.0..4.0, 9.0..10.0
    assert s.busy_s == pytest.approx(3.5)
    assert s.idle_share == pytest.approx(0.65)
    # gaps: 4..9 (midpoint 6.5 in bench.wait), 10..11 (engine call),
    # 2.5..3 (engine call)
    assert s.idle_gaps[0] == ["bench.wait", pytest.approx(5.0)]
    assert s.idle_gaps[1] == ["bench.engine_call", pytest.approx(1.0)]
    assert s.idle_gaps[2] == ["bench.engine_call", pytest.approx(0.5)]
    assert s.device_ops[0] == ["fusion.1", pytest.approx(2.0)]
    assert s.program_runs == 2 and s.program_s == pytest.approx(3.5)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([e for e in _events() if e.name != "bench.window"],
                  program="decode_step")


def test_loader_reads_host_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.engine_call"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load(tr.find_xplane(tmp_path))
    names = [e.name for e in events if e.kind == "host"]
    assert names.count("bench.engine_call") == 3
    assert "bench.window" in names


def test_recorded_v5e_decode_step_matches_a_brute_force_count():
    """One engine call (42 ms) recorded on a TPU v5 lite during a
    qwen3-0.6b decode-batch run: one decode-step execution, then the
    host loop's per-slot logits slicing, with the window set to it."""
    import json

    import numpy as np

    raw = json.loads((tiny.BENCH / "tests" / "data" /
                      "v5e_decode_step.json").read_text())
    events = [tr.Event(*e) for e in raw]
    s = tr.reduce(events, program="decode_step")
    win = next(e for e in events if e.name == "bench.window")
    # busy time on a 100 ns grid
    grid = np.zeros(int(round(win.dur * 1e7)), bool)
    for e in events:
        if e.kind == "op":
            a = max(int(round((e.start - win.start) * 1e7)), 0)
            b = min(int(round((e.start + e.dur - win.start) * 1e7)), len(grid))
            grid[a:b] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-7, abs=5e-6)
    assert s.window_s == pytest.approx(0.042)
    assert s.program_runs == 1 and s.program_s == pytest.approx(0.025, abs=1e-3)
    assert 0.3 < s.idle_share < 0.5  # the host's per-slot sampling
    assert s.idle_gaps[0][0] == "bench.engine_call"
    assert all(name.startswith("%") for name, _ in s.device_ops)
