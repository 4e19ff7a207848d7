"""What a run leaves for the metric readers, and the reductions several
readers share. Each reader (``bench/metrics/<name>.py``) takes a
``Context`` and returns a number, or None where it finds nothing to
read."""

from __future__ import annotations

import dataclasses

import numpy as np

from harness import counts
from harness.client import Run
from harness.trace import Summary


@dataclasses.dataclass
class Context:
    run: Run
    dims: counts.Dims
    peaks: dict
    setup_s: float
    peak_bytes: int
    planned_state_bytes: int | None
    trace: Summary | None = None

    @property
    def window_s(self) -> float:
        return self.run.t_close - self.run.t0

    # ------------------------------------------------------- requests
    def due_in_window(self) -> list:
        """Requests that fell due inside the window (not set-up fill)."""
        r = self.run
        return [rec for rec in r.records
                if not rec.in_setup and r.t0 <= rec.due < r.t_end]

    def token_gaps(self) -> list[float]:
        """Seconds between consecutive tokens of one request, both
        emitted inside the window."""
        gaps = []
        for rec in self.run.records:
            times = [t for t in rec.token_times if t >= self.run.t0]
            gaps.extend(np.diff(times).tolist())
        return gaps

    # ------------------------------------------------------- calls
    def pure_decode_calls(self) -> list:
        return [c for c in self.run.calls if not c.admitted and c.emitted]

    def admitting_calls(self) -> list:
        return [c for c in self.run.calls if c.admitted]

    def dispatches(self, since: float | None = None) -> list[list[tuple[int, bool]]]:
        """Every decode step the window's calls (those starting at or
        after ``since``) ran, in order, as the ``(context, head)`` of each
        slot it advanced: an admitted prompt's tokens but the last fill
        the cache one step each, then one wave advances every active
        slot."""
        steps = []
        for call in self.run.calls:
            if since is not None and call.start < since:
                continue
            for rec in call.admitted:
                steps.extend([[(j + 1, False)] for j in range(rec.prompt_len - 1)])
            if call.wave_slots:
                steps.append([(pos + 1, True) for pos in call.wave_slots])
        return steps


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None
