"""One registry over the process-wide instrumentation: counters and spans.

The repo instruments its hot paths with module-global counters
(``tracer.TRACE_CALLS``, ``planner.PLAN_CALLS``,
``unified.STATE_PLAN_CALLS``, ``engine.HOST_SYNCS``,
``residency.COMPILE_CALLS``, ``residency.DECODE_DISPATCHES``,
``engine.SAMPLE_FETCHES``, ``attention.LANE_ROW_ATTENTION``) that
tests, CI and benches snapshot/delta to pin caching and sync behaviour.
Before this module each call site hand-rolled the same
``t0, p0, s0 = tracer.TRACE_CALLS, planner.PLAN_CALLS, ...`` boilerplate;
here they are one named registry:

    from repro.analysis import counters

    with counters.capture() as cap:
        engine.generate(...)
    assert cap.delta("trace_calls") == 0
    assert cap.delta("host_syncs") == 1

Host spans of the serving path go through :func:`span`, a named
interval on the profiler's host timeline, which shares its clock with
the device trace:

    with counters.span("repro.prompt_feed", rid=7, tokens=31):
        ...

Counters are looked up lazily by (module, attribute), and the profiler
is imported on first use, so importing this module does not drag in
jax via ``repro.runtime.engine``.
"""

from __future__ import annotations

import contextlib
import importlib
from typing import Iterator

# name -> (module, attribute) holding an int module-global
REGISTRY: dict[str, tuple[str, str]] = {
    "trace_calls": ("repro.trace.jaxpr_liveness", "TRACE_CALLS"),
    "plan_calls": ("repro.core.planner", "PLAN_CALLS"),
    "state_plan_calls": ("repro.core.unified", "STATE_PLAN_CALLS"),
    "host_syncs": ("repro.runtime.engine", "HOST_SYNCS"),
    "compile_calls": ("repro.runtime.residency", "COMPILE_CALLS"),
    # one per execution of the single-wave decode program, prompt-feed
    # dispatches included (scan blocks are not counted). HOST_SYNCS
    # still counts host-loop waves and scan blocks only, not prompt feed.
    "decode_dispatches": ("repro.runtime.residency", "DECODE_DISPATCHES"),
    # one per host-loop wave: the device-to-host fetch its sampling makes
    # (greedy picks or the wave's logits), on either sampling path
    "sample_fetches": ("repro.runtime.engine", "SAMPLE_FETCHES"),
    # one per attention layer traced through the lane-row kernel, which
    # reads K and V where they lie in the state buffer (trace time)
    "lane_row_attention": ("repro.models.attention", "LANE_ROW_ATTENTION"),
}


def _module(name: str):
    mod_name, _ = REGISTRY[name]
    return importlib.import_module(mod_name)


def read(name: str) -> int:
    """Current value of one registered counter."""
    mod_name, attr = REGISTRY[name]
    return getattr(importlib.import_module(mod_name), attr)


def snapshot(names: tuple[str, ...] | None = None) -> dict[str, int]:
    """Read every (or the named) registered counters at once."""
    return {n: read(n) for n in (names or tuple(REGISTRY))}


def reset(names: tuple[str, ...] | None = None) -> None:
    """Zero the named counters (all by default)."""
    for n in names or tuple(REGISTRY):
        _, attr = REGISTRY[n]
        setattr(_module(n), attr, 0)


def span(name: str, **args):
    """A host span for ``with``: ``jax.profiler.TraceAnnotation`` under
    ``name``, its keyword arguments written as the event's stats. The
    serving path's spans are named ``repro.*``. Outside a profiler
    session the span records nothing and costs about a microsecond."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **args)


class Capture:
    """Deltas of the registered counters since ``capture()`` entry."""

    def __init__(self, names: tuple[str, ...]):
        self.names = names
        self.start = snapshot(names)

    def delta(self, name: str) -> int:
        return read(name) - self.start[name]

    def deltas(self) -> dict[str, int]:
        return {n: self.delta(n) for n in self.names}


@contextlib.contextmanager
def capture(*names: str) -> Iterator[Capture]:
    """Snapshot counters on entry; ``cap.delta(name)`` reads live deltas.

    With no arguments captures every registered counter. Does not reset
    the underlying globals — deltas are relative to entry, so captures
    nest safely.
    """
    yield Capture(names or tuple(REGISTRY))
