"""Reduce a profiler trace of the window to device busy time, idle gaps,
the heaviest device operations and the executions of one program.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
the few events the reduction needs, as plain ``Event`` tuples:

* device operations: the ``XLA Ops`` line of each ``/device:*`` plane
  (``XLA Modules`` where a plane has no op line);
* program executions: the ``XLA Modules`` line of each device plane;
* host spans of the benchmark itself (names starting ``bench.``).

``reduce`` works on those tuples alone, so it is tested on a small
recorded list of events (``bench/tests/data``) without JAX.
"""

from __future__ import annotations

import dataclasses
import glob
from pathlib import Path
from typing import NamedTuple


class Event(NamedTuple):
    kind: str  # "op" | "module" | "host"
    plane: str
    name: str
    start: float  # seconds, on the trace's own clock
    dur: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the device planes
    device_ops: list  # [[name, seconds]] heaviest first, at most 10
    idle_gaps: list  # [[host span, seconds]] longest first, at most 10
    program_s: float  # device time of the matched program's executions
    program_runs: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def short_name(name: str) -> str:
    """An XLA op's event name is its whole HLO instruction; keep the
    instruction's name and the start of its result type."""
    head, sep, rest = name.partition(" = ")
    return f"{head} {rest[:48]}" if sep else name


def load(path: Path) -> list[Event]:
    import sys

    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    events: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if op_line is None:
                continue
            for ev in op_line.events:
                events.append(Event("op", plane.name,
                                    sys.intern(short_name(ev.name)),
                                    ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
            if "XLA Modules" in lines:
                for ev in lines["XLA Modules"].events:
                    events.append(Event("module", plane.name, ev.name,
                                        ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        events.append(Event("host", plane.name, ev.name,
                                            ev.start_ns * 1e-9,
                                            ev.duration_ns * 1e-9))
    return events


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Event, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(ev.start, lo), min(ev.start + ev.dur, hi)
    return (a, b) if b > a else None


def _label(t: float, spans: list[Event]) -> str:
    """The innermost benchmark span running on the host at ``t``."""
    inside = [s for s in spans if s.start <= t <= s.start + s.dur
              and s.name != "bench.window"]
    if not inside:
        return "host.other"
    return max(inside, key=lambda s: s.start).name


def reduce(events: list[Event], *, program: str, top: int = 10) -> Summary:
    """Reduce the events inside the host span ``bench.window``.

    ``program`` is a substring of the name of the program whose
    executions are timed (``XLA Modules`` events)."""
    windows = [e for e in events if e.kind == "host" and e.name == "bench.window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    win = max(windows, key=lambda e: e.dur)
    lo, hi = win.start, win.start + win.dur
    spans = [e for e in events if e.kind == "host"]
    planes = sorted({e.plane for e in events if e.kind == "op"})
    busy_total = 0.0
    busy_intervals: list[tuple[float, float]] = []
    op_time: dict[str, float] = {}
    for plane in planes:
        ivs = []
        for e in events:
            if e.kind == "op" and e.plane == plane:
                iv = _clip(e, lo, hi)
                if iv:
                    ivs.append(iv)
                    op_time[e.name] = op_time.get(e.name, 0.0) + iv[1] - iv[0]
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged)
        busy_intervals.extend(merged)
    n = max(len(planes), 1)
    # idle gaps: where no device ran an operation
    gaps = []
    cursor = lo
    for a, b in _union(busy_intervals) + [(hi, hi)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label((a + b) / 2, spans), b - a] for a, b in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    runs = [e for e in events if e.kind == "module" and program in e.name
            and lo <= e.start and e.start + e.dur <= hi]
    return Summary(
        window_s=hi - lo,
        busy_s=busy_total / n,
        device_ops=[[k, v] for k, v in ops],
        idle_gaps=idle,
        program_s=sum(e.dur for e in runs),
        program_runs=len(runs),
    )
