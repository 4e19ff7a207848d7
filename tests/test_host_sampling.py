"""The host loop's sampling: one fetch per wave.

Greedy picks are taken on the device (``sampling.greedy_tokens_jit``)
and only the ``n_slots`` int32 picks come back; sampled runs fetch the
wave's logits once and draw each row on the host. The reference is the
loop as it sampled before: each active slot's logits row fetched on its
own, and greedy picks taken by numpy's argmax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import counters
from repro.configs.base import get_reduced
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine

BACKENDS = {
    "pytree": {"state_residency": False},
    "resident": {},
    "paged": {"page_size": 1024},
}


class _PerRowEngine(InferenceEngine):
    """The reference: one device-to-host fetch per active slot, and the
    greedy pick by numpy's argmax of the fetched row."""

    def _fetch_rows(self, logits):
        return {slot: np.asarray(logits[slot]) for slot in self._active}

    def _sample_token(self, row):
        if self.greedy:
            return int(row.argmax())
        return super()._sample_token(row)


def _params(arch, weights):
    cfg = get_reduced(arch)
    params = Model.for_config(cfg).init(jax.random.PRNGKey(0))
    if weights == "zero":  # every logit 0: the pick is a tie over the vocab
        params = jax.tree_util.tree_map(jnp.zeros_like, params)
    return cfg, params


def _serve(engine_cls, cfg, params, backend, **kw):
    """Five requests on two slots, so slots are reused; returns each
    request's tokens and the slot log."""
    engine = engine_cls(cfg, params, n_slots=2, max_len=32,
                        **BACKENDS[backend], **kw)
    rng = np.random.default_rng(3)
    for n, new in ((4, 5), (3, 2), (5, 4), (2, 3), (4, 2)):
        engine.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                      max_new_tokens=new)
    done = engine.run_until_done()
    assert len(done) == 5
    return {r.request_id: r.tokens for r in done}, engine.slot_log


@pytest.mark.parametrize("weights", ["random", "zero"])
@pytest.mark.parametrize("backend", list(BACKENDS))
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-2.7b"])
def test_greedy_picks_match_per_row_argmax(arch, backend, weights):
    cfg, params = _params(arch, weights)
    got, log = _serve(InferenceEngine, cfg, params, backend)
    want, want_log = _serve(_PerRowEngine, cfg, params, backend)
    assert got == want
    assert log == want_log
    slots = [slot for slot, *_ in log]
    assert len(set(slots)) < len(slots), "slots must be reused"
    if weights == "zero":
        # the device and numpy both take the first maximal index
        assert all(t == 0 for toks in got.values() for t in toks)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-2.7b"])
def test_sampled_draws_match_per_row_fetch(arch):
    """One fetch of the wave's logits leaves the draws as they were: the
    same rows go to the same generator in the same slot order. A fixed
    ``sample_seed`` reproduces the run."""
    cfg, params = _params(arch, "random")
    kw = {"greedy": False, "sample_seed": 11, "temperature": 0.9,
          "top_k": 40}
    got, log = _serve(InferenceEngine, cfg, params, "resident", **kw)
    assert _serve(InferenceEngine, cfg, params, "resident", **kw) == (got,
                                                                      log)
    assert _serve(_PerRowEngine, cfg, params, "resident", **kw) == (got, log)


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_one_sample_fetch_per_wave_inside_the_span(greedy, monkeypatch):
    """Over N waves ``sample_fetches`` and ``host_syncs`` both read N,
    whatever the number of active slots, and each fetch happens inside
    the wave's ``repro.sample`` span, which carries ``rows``."""
    cfg, params = _params("qwen3-0.6b", "random")
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32,
                             greedy=greedy, sample_seed=0)
    for n in (3, 4):
        engine.submit(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=8)
    events = []
    span = counters.span

    class _Recorded:
        def __init__(self, name, args):
            self.name, self.args, self.inner = name, args, span(name, **args)

        def __enter__(self):
            events.append(("enter", self.name, self.args))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            events.append(("exit", self.name, {}))
            return self.inner.__exit__(*exc)

    fetch = InferenceEngine._fetch_rows

    def fetch_rows(self, logits):
        events.append(("fetch", "", {}))
        return fetch(self, logits)

    monkeypatch.setattr(counters, "span", lambda n, **a: _Recorded(n, a))
    monkeypatch.setattr(InferenceEngine, "_fetch_rows", fetch_rows)
    n_waves = 5
    with counters.capture("sample_fetches", "host_syncs") as cap:
        for _ in range(n_waves):
            assert engine.step() == []
    assert cap.delta("sample_fetches") == n_waves
    assert cap.delta("host_syncs") == n_waves
    sampling = [e for e in events
                if e[1] in ("repro.sample", "")]
    assert sampling == [("enter", "repro.sample", {"rows": 2}),
                        ("fetch", "", {}),
                        ("exit", "repro.sample", {})] * n_waves


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
@pytest.mark.parametrize("backend", list(BACKENDS))
def test_no_compile_after_the_first_wave(backend, greedy):
    """The argmax compiles with the first wave; later waves, through a
    retirement and a slot's reuse, compile nothing (the backend compile
    event the benchmark's window check counts)."""
    cfg, params = _params("qwen3-0.6b", "random")
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32,
                             greedy=greedy, sample_seed=0, **BACKENDS[backend])
    for new in (2, 6, 3):
        engine.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=new)
    engine.step()
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        done = engine.run_until_done()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(done) == 3
    assert [slot for slot, *_ in engine.slot_log].count(0) == 2, \
        "the third request must reuse the first one's slot"
    assert compiles == []
