"""The instrumentation-counter registry (repro.analysis.counters)."""

import pytest

from repro.analysis import counters


def test_registry_reads_and_resets():
    from repro.core import planner
    from repro.core.records import make_records

    counters.reset(("plan_calls",))
    assert counters.read("plan_calls") == 0
    planner.plan_records(
        make_records([(0, 1, 64)]), use_cache=False, graph_name="counters-t1"
    )
    assert counters.read("plan_calls") == 1
    snap = counters.snapshot(("plan_calls", "state_plan_calls"))
    assert snap["plan_calls"] == 1
    counters.reset(("plan_calls",))
    assert counters.read("plan_calls") == 0


def test_capture_deltas_without_reset():
    from repro.core import planner
    from repro.core.records import make_records

    recs = make_records([(0, 1, 64), (1, 2, 32)])
    planner.plan_records(recs, use_cache=False, graph_name="counters-t2")
    before = counters.read("plan_calls")
    with counters.capture("plan_calls", "state_plan_calls") as outer:
        planner.plan_records(recs, use_cache=False, graph_name="counters-t3")
        with counters.capture("plan_calls") as inner:
            planner.plan_records(
                recs, use_cache=False, graph_name="counters-t4"
            )
        assert inner.delta("plan_calls") == 1
        assert outer.delta("plan_calls") == 2
        assert outer.delta("state_plan_calls") == 0
    assert outer.deltas()["plan_calls"] == 2
    # capture never resets the underlying globals
    assert counters.read("plan_calls") == before + 2


def test_capture_defaults_to_full_registry():
    with counters.capture() as cap:
        pass
    assert set(cap.deltas()) == set(counters.REGISTRY)
    assert all(d == 0 for d in cap.deltas().values())


@pytest.mark.parametrize("backend", [
    {"state_residency": False},  # pytree
    {},  # resident
    {"page_size": 1024},  # paged
], ids=["pytree", "resident", "paged"])
def test_decode_dispatches_counts_every_decode_execution(backend):
    """A 5-token prompt is fed in 4 executions (all but its last token),
    then each of 3 waves is one more: 4 + 3. Host syncs count the waves
    only."""
    import jax
    import numpy as np

    from repro.configs.base import get_reduced
    from repro.models.api import Model
    from repro.runtime.engine import InferenceEngine

    cfg = get_reduced("qwen3-0.6b")
    params = Model.for_config(cfg).init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32, **backend)
    engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    with counters.capture("decode_dispatches", "host_syncs") as cap:
        engine.step()
        assert cap.delta("decode_dispatches") == 4 + 1
        engine.step()
        finished = engine.step()
    assert [len(r.tokens) for r in finished] == [3]
    assert cap.delta("decode_dispatches") == 4 + 3
    assert cap.delta("host_syncs") == 3
