"""The program's own instrumentation in the traced part of a window: its
host spans (``repro.*``, with their arguments) and the device time of
operations emitted under a name scope.

``harness.trace`` keeps only what the benchmark's first readers need:
the benchmark's own spans and each op's name and times. This module
reads the same ``.xplane.pb`` again for the rest:

* host spans named ``repro.`` and their arguments (the event's stats);
* each device op's name-scope path. The TPU keeps it in the op's event
  metadata (stat ``tf_op``, e.g. ``jit(decode_step)/state.unpack/...``),
  which ``jax.profiler.ProfileData`` does not expose, so ``op_scopes``
  reads those fields of the ``XSpace`` proto. On the CPU no op carries
  one.

A traced run's readers find that file through ``of(ctx)``: the trace
that ``bench/run.py`` writes under ``.bench_out/trace`` is still there
while its readers run. ``reduce`` works on ``Event`` tuples alone, so
it is tested on hand-made and recorded events without JAX. A trace of
a program without these spans or scopes reduces to empty lists and
zero seconds, and the readers built on it return None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from pathlib import Path
from typing import NamedTuple

from harness.trace import _union, find_xplane

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"
PROGRAM = "decode_step"  # the served decode jit's name, as bench/run.py has it
SCOPE_STAT = "tf_op"  # the op event-metadata stat that holds its scope path
# the name scopes of the state backend's copies (runtime/residency.py,
# runtime/paging.py): the state buffer into the cache pytree, and back
STATE_SCOPES = ("state.unpack", "state.pack")


class Event(NamedTuple):
    kind: str  # "op" | "module" | "host"
    plane: str
    name: str
    start: float  # seconds, on the trace's own clock
    dur: float
    scope: str = ""  # op: its name-scope path
    args: tuple = ()  # program span: its (name, value) arguments


@dataclasses.dataclass
class Spans:
    window_s: float  # the traced part (host span ``bench.window``)
    host: list[Event]  # program spans wholly inside the traced part
    idle: list[tuple[float, float]]  # where no device ran an operation
    program_s: float  # device time of the program's executions
    scoped_s: dict[str, float]  # scope -> device time of its ops in them

    def named(self, name: str) -> list[Event]:
        return [e for e in self.host if e.name == name]

    def durations(self, name: str) -> list[float]:
        return [e.dur for e in self.named(name)]

    def idle_within(self, name: str) -> float:
        """Seconds in which no device ran an operation while a span
        ``name`` was open on the host."""
        ends = [b for _, b in self.idle]  # disjoint and in order
        total = 0.0
        for lo, hi in _union([(e.start, e.start + e.dur)
                              for e in self.named(name)]):
            i = bisect.bisect_right(ends, lo)
            while i < len(self.idle) and self.idle[i][0] < hi:
                a, b = self.idle[i]
                total += min(b, hi) - max(a, lo)
                i += 1
        return total


# ------------------------------------------------------------ loading
def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field number, value)`` of one protobuf message in ``buf[lo:hi]``:
    an int for a varint, ``(start, end)`` for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, value


def op_scopes(path: Path) -> dict[str, dict[str, str]]:
    """Per device plane, each op's event name to the ``SCOPE_STAT`` stat
    of its event metadata, read from the ``XSpace`` proto (tsl's
    xplane.proto: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5; map entries key 1, value 2; XEventMetadata.name 2,
    stats 5; XStatMetadata.name 2; XStat.metadata_id 1, str_value 5,
    ref_value 7, a ref naming the stat metadata entry that holds the
    string). The events themselves (XPlane.lines) are skipped."""
    buf = memoryview(Path(path).read_bytes())

    def text(span) -> str:
        return bytes(buf[span[0]:span[1]]).decode()

    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = text(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry.get(2, (0, 0))))
                stat_names[entry.get(1, 0)] = text(meta[2]) if 2 in meta else ""
        if not name.startswith("/device:"):
            continue
        scopes = out.setdefault(name, {})
        for entry in metas:
            meta = dict(_fields(buf, *entry)).get(2)
            if meta is None:
                continue
            ev_name, scope = "", ""
            for h, v in _fields(buf, *meta):
                if h == 2:
                    ev_name = text(v)
                elif h == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = text(stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope:
                scopes[ev_name] = scope
    return out


def load(path: Path) -> list[Event]:
    """The events ``reduce`` needs: device ops with their scopes and
    program executions (as ``harness.trace.load`` reads them), the
    benchmark's ``bench.window`` and the program's ``repro.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    scopes = op_scopes(path)
    events: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if op_line is None:
                continue
            plane_scopes = scopes.get(plane.name, {})
            for ev in op_line.events:
                events.append(Event("op", plane.name, "", ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9,
                                    plane_scopes.get(ev.name, "")))
            if "XLA Modules" in lines:
                for ev in lines["XLA Modules"].events:
                    events.append(Event("module", plane.name, ev.name,
                                        ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro.") or ev.name == "bench.window":
                        events.append(Event("host", plane.name, ev.name,
                                            ev.start_ns * 1e-9,
                                            ev.duration_ns * 1e-9, "",
                                            tuple(ev.stats)))
    return events


# ---------------------------------------------------------- reduction
def in_scope(scope: str, name: str) -> bool:
    """Whether an op's name-scope path passes through scope ``name``."""
    return name in scope.split("/")


def reduce(events: list[Event], *, program: str = PROGRAM) -> Spans:
    """The program's spans, device idle time and the device time under
    the state scopes inside the host span ``bench.window`` (the longest,
    as ``harness.trace.reduce`` takes it).

    ``program`` is a substring of the name of the program whose
    executions (``XLA Modules`` events wholly inside the traced part)
    are timed; a scoped op counts where it began inside one of them."""
    windows = [e for e in events if e.kind == "host" and e.name == "bench.window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    win = max(windows, key=lambda e: e.dur)
    lo, hi = win.start, win.start + win.dur
    host = [e for e in events if e.kind == "host" and e.name.startswith("repro.")
            and lo <= e.start and e.start + e.dur <= hi]
    busy = []
    for e in events:
        if e.kind == "op":
            a, b = max(e.start, lo), min(e.start + e.dur, hi)
            if b > a:
                busy.append((a, b))
    idle, cursor = [], lo
    for a, b in _union(busy) + [(hi, hi)]:
        if a > cursor:
            idle.append((cursor, a))
        cursor = max(cursor, b)
    runs: dict[str, list[tuple[float, float]]] = {}
    for e in events:
        if (e.kind == "module" and program in e.name
                and lo <= e.start and e.start + e.dur <= hi):
            runs.setdefault(e.plane, []).append((e.start, e.start + e.dur))
    for ivs in runs.values():
        ivs.sort()
    starts = {plane: [a for a, _ in ivs] for plane, ivs in runs.items()}
    scoped: dict[str, float] = {}
    for e in events:
        if e.kind != "op" or not e.scope or e.plane not in runs:
            continue
        hit = [s for s in STATE_SCOPES if in_scope(e.scope, s)]
        if not hit:
            continue
        i = bisect.bisect_right(starts[e.plane], e.start) - 1
        if i >= 0 and e.start < runs[e.plane][i][1]:
            scoped[hit[0]] = scoped.get(hit[0], 0.0) + e.dur
    return Spans(
        window_s=hi - lo,
        host=host,
        idle=idle,
        program_s=sum(b - a for ivs in runs.values() for a, b in ivs),
        scoped_s=scoped,
    )


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> Spans:
    return reduce(load(Path(path)))


def of(ctx) -> Spans | None:
    """The program's spans of a traced run, for its readers: the trace
    under ``TRACE_DIR``, reduced once for all of them. None for an
    untraced run, and where the file there is not this run's trace (its
    traced part differs from ``ctx.trace``'s)."""
    if ctx.trace is None:
        return None
    try:
        path = find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    s = _reduced(str(path), path.stat().st_mtime_ns)
    return s if s.window_s == ctx.trace.window_s else None
