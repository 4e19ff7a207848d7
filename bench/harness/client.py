"""Drive the engine with a traffic stream and record, on the host clock,
what every call into the engine did.

The entry under test is the engine's public surface: ``submit``,
``step`` (``step_block`` when the deployment's ``block_size`` is above
1) and ``unfinished_requests``. A request's tokens are read from the
growth of ``len(req.tokens)`` after each call; the state backend blocks
on the new state, so a call returns after its device work, and a token
counts as emitted when the call that made it returned.

Host spans (``jax.profiler.TraceAnnotation``) mark what the host does,
so that a traced run can say what each idle gap of the device waited
for: ``bench.wait`` (no request due), ``bench.submit``,
``bench.engine_call`` and ``bench.bookkeeping``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax

from harness.traffic import Request as Planned
from harness.traffic import Traffic


@dataclasses.dataclass
class Record:
    planned: Planned
    rid: int
    due: float
    submit: float
    req: object = None  # the engine's request object, while it lives
    tokens: list | None = None  # its tokens, kept when the engine is freed
    admitted: bool = False
    admit_call: int = -1  # the window call that admitted it (-1: set-up)
    token_times: list = dataclasses.field(default_factory=list)
    done: float | None = None
    in_setup: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.planned.prompt)


@dataclasses.dataclass
class Call:
    start: float
    end: float
    admitted: list  # Records admitted during this call
    emitted: int  # tokens emitted
    # the position each slot the call's decode wave advanced was at
    wave_slots: list


@dataclasses.dataclass
class Run:
    records: list[Record]
    calls: list[Call]  # calls inside the window
    t0: float  # window start
    t_end: float  # nominal window end
    t_close: float  # end of the last call of the window
    t_trace: float | None = None  # start of the traced part of the window


class Client:
    def __init__(self, engine, traffic: Traffic, *, spans: bool,
                 clock=time.perf_counter):
        self.engine = engine
        self.traffic = traffic
        self.clock = clock
        self.block = int(getattr(engine, "block_size", 1)) > 1
        self._span = (jax.profiler.TraceAnnotation if spans
                      else lambda name: contextlib.nullcontext())
        self.records: list[Record] = []
        self._live: dict[int, Record] = {}  # rid -> record not yet finished
        self._stream = collections.deque(traffic.stream)

    # ------------------------------------------------------------ calls
    def _submit(self, planned: Planned, due: float, *, setup=False) -> Record:
        with self._span("bench.submit"):
            rid = self.engine.submit(planned.prompt, max_new_tokens=planned.max_new)
            req = self.engine.unfinished_requests()[-1]
            assert req.request_id == rid
            rec = Record(planned, rid, due, self.clock(), req, in_setup=setup)
            self.records.append(rec)
            self._live[rid] = rec
        return rec

    def _call(self, index: int) -> Call:
        # the call admits queued requests, then advances every admitted one
        live = list(self._live.values())
        with self._span("bench.engine_call"):
            start = self.clock()
            finished = (self.engine.step_block() if self.block
                        else self.engine.step())
            end = self.clock()
        with self._span("bench.bookkeeping"):
            admitted, wave, emitted = [], [], 0
            for rec in live:
                req = rec.req
                n_before = len(rec.token_times)
                if not rec.admitted and req.admitted_wave >= 0:
                    rec.admitted = True
                    rec.admit_call = index
                    admitted.append(rec)
                grown = len(req.tokens) - n_before
                if grown > 0:
                    wave.append(rec.prompt_len - 1 + n_before)
                    rec.token_times.extend([end] * grown)
                    emitted += grown
            for req in finished:
                rec = self._live.pop(req.request_id)
                rec.done = end
        return Call(start, end, admitted, emitted, wave)

    # ------------------------------------------------------------ phases
    def setup(self) -> None:
        """Compile and warm every shape the window uses, outside it: a
        closed loop fills every slot with its first requests and runs a
        wave; an open loop serves one short request per slot."""
        now = self.clock()
        for p in self.traffic.initial + self.traffic.warmup:
            self._submit(p, now, setup=True)
        self._call(-1)
        while self.traffic.warmup and self.engine.unfinished_requests():
            self._call(-1)
        if not self.traffic.closed:
            self.records.clear()
            self._live.clear()

    def window(self, seconds: float, *, trace_start=None,
               trace_seconds: float = 0.0) -> Run:
        """Serve the stream for ``seconds``. With ``trace_start``, call it
        ``trace_seconds`` before the end (between engine calls) and mark
        the traced part with the host span ``bench.window``."""
        calls: list[Call] = []
        t0 = self.clock()
        t_end = t0 + seconds
        closed = self.traffic.closed
        trace_at = (max(t_end - trace_seconds, t0) if trace_start
                    else float("inf"))
        t_trace = None
        # a closed loop replaces requests that finished in set-up at once
        if closed:
            for _ in range(self.traffic.concurrency - len(self._live)):
                self._submit(self._stream.popleft(), t0)
        with contextlib.ExitStack() as traced:
            while True:
                now = self.clock()
                if now >= t_end:
                    break
                if now >= trace_at:
                    trace_start()
                    t_trace, trace_at = self.clock(), float("inf")
                    traced.enter_context(self._span("bench.window"))
                while (not closed and self._stream
                       and t0 + self._stream[0].offset_s <= now):
                    p = self._stream.popleft()
                    self._submit(p, t0 + p.offset_s)
                if self._live:
                    n_live = len(self._live)
                    call = self._call(len(calls))
                    calls.append(call)
                    if closed:
                        for _ in range(n_live - len(self._live)):
                            self._submit(self._stream.popleft(), call.end)
                    continue
                if not self._stream:
                    break
                with self._span("bench.wait"):
                    wake = min(t0 + self._stream[0].offset_s, t_end, trace_at)
                    time.sleep(max(wake - self.clock(), 0.0))
        # requests that fell due while the last call ran are due in the
        # window too: recorded now, they count at their wait so far
        while (not closed and self._stream
               and self._stream[0].offset_s < seconds):
            p = self._stream.popleft()
            self._submit(p, t0 + p.offset_s)
        t_close = calls[-1].end if calls else self.clock()
        return Run(self.records, calls, t0, t_end, max(t_close, t_end), t_trace)
