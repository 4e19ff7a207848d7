"""Serving-engine tests: continuous batching correctness + memory report.

The reference for each request is single-request decoding (B=1) with the
same params — the engine must produce identical greedy tokens even when
requests share a batch, arrive staggered, and reuse slots (active-mask
and per-slot-position correctness, incl. frozen mamba states).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_reduced
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine


def _teacher_forced_logits(cfg, params, prompt, emitted):
    """B=1 decode replaying ``prompt + emitted`` (the ENGINE's trajectory);
    returns the logits used to choose each emitted token. Comparing in
    teacher-forced mode sidesteps CPU XLA's non-bitwise-deterministic
    reductions: a numeric argmax tie in the engine would otherwise send
    the reference down a different trajectory entirely."""
    model = Model.for_config(cfg)
    caches = model.init_cache(1, 64)
    decode = jax.jit(
        lambda p, t, c, pos, act: model.decode_step(p, t, c, pos, active=act)
    )
    act = jnp.ones((1,), bool)
    seq = list(prompt) + list(emitted)
    step_logits = []
    for pos, t in enumerate(seq[:-1]):
        logits, caches = decode(
            params, jnp.asarray([[t]], jnp.int32), caches,
            jnp.asarray([pos], jnp.int32), act,
        )
        if pos >= len(prompt) - 1:
            step_logits.append(np.asarray(logits)[0])
    return step_logits


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-2.7b", "zamba2-7b"])
def test_engine_matches_single_request_reference(arch):
    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 6, 3)]
    max_new = 5

    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    for pr in prompts:
        engine.submit(pr, max_new_tokens=max_new)
    done = engine.run_until_done()
    assert len(done) == len(prompts)
    by_id = {r.request_id: r for r in done}

    for rid, pr in enumerate(prompts):
        got = by_id[rid].tokens
        ref_logits = _teacher_forced_logits(cfg, params, pr, got)
        assert len(ref_logits) == len(got)
        for i, (g, row) in enumerate(zip(got, ref_logits)):
            w = int(row.argmax())
            if g == w:
                continue
            # the engine's pick must be within float noise of the
            # reference's best at the SAME state (numeric argmax tie)
            gap = float(row[w]) - float(row[g])
            assert gap < 1e-3, (
                f"{arch} req {rid} step {i}: engine chose {g}, reference "
                f"argmax {w}, logit gap {gap} too large to be a tie"
            )


def test_engine_slot_reuse_is_interval_valid():
    """Slot reuse must respect usage intervals — the §4 invariant at the
    request level (no two requests share a slot while both in flight)."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    rng = np.random.default_rng(1)
    for _ in range(5):
        engine.submit(rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                      max_new_tokens=3)
    done = engine.run_until_done()
    assert len(done) == 5
    assert len(engine.slot_log) == 5
    by_slot: dict[int, list[tuple[int, int]]] = {}
    for slot, first, last, rid in engine.slot_log:
        by_slot.setdefault(slot, []).append((first, last))
    reused = any(len(v) > 1 for v in by_slot.values())
    assert reused, "with 5 requests and 2 slots, slots must be reused"
    for slot, ivals in by_slot.items():
        ivals.sort()
        for (f1, l1), (f2, l2) in zip(ivals, ivals[1:]):
            assert l1 <= f2, f"slot {slot}: intervals {ivals} overlap"


def test_sampling_slots_with_identical_logits_can_diverge():
    """Regression: per-slot default_rng(self._wave) seeded every slot in a
    wave identically, so equal logits always produced equal tokens. The
    engine-owned generator must let consecutive draws differ."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32,
                             greedy=False, sample_seed=0)
    row = np.zeros(cfg.vocab, np.float32)  # identical (flat) logits
    draws = [engine._sample_token(row) for _ in range(32)]
    assert len(set(draws)) > 1, "identical logits must not pin the sample"
    # a fixed seed still makes whole runs reproducible
    engine2 = InferenceEngine(cfg, params, n_slots=2, max_len=32,
                              greedy=False, sample_seed=0)
    assert [engine2._sample_token(row) for _ in range(32)] == draws
    # and a different seed gives a different trajectory
    engine3 = InferenceEngine(cfg, params, n_slots=2, max_len=32,
                              greedy=False, sample_seed=1)
    assert [engine3._sample_token(row) for _ in range(32)] != draws


def test_engine_accepts_pre_searched_graph():
    """The outer search hands the engine a reordered/fused graph through
    ``PlanSession.from_spec``; the engine plans it instead of the
    default-order trace (decode outputs are unchanged — the plan is a
    memory artifact, not an executor)."""
    import jax.numpy as jnp

    from repro.core.fusion_search import fusion_search
    from repro.core.unified import PlanSession, PlanSpec
    from repro.trace.jaxpr_liveness import trace_graph

    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_slots, max_len = 2, 32
    caches = model.init_cache(n_slots, max_len)
    graph = trace_graph(
        lambda p, t, c, pos, act: model.decode_step(p, t, c, pos, active=act),
        params,
        jnp.zeros((n_slots, 1), jnp.int32),
        caches,
        jnp.zeros((n_slots,), jnp.int32),
        jnp.ones((n_slots,), bool),
        name=f"{cfg.name}-decode",
    )
    searched = fusion_search(graph)
    engine = InferenceEngine(
        cfg, params, n_slots=n_slots, max_len=max_len,
        session=PlanSession.from_spec(PlanSpec(graph=searched.graph)),
    )
    plan = engine.memory_report.activation_plan
    assert plan.total_size == searched.plan.total_size
    assert plan.total_size <= searched.baseline_plan.total_size
    # the engine still serves correctly off the searched plan
    engine.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    done = engine.run_until_done()
    assert len(done) == 1 and len(done[0].tokens) == 3


def test_session_from_spec_pins_the_strategy():
    """A PlanSession over a PlanSpec plans the activations with the
    strategy it names."""
    from repro.core.unified import PlanSession, PlanSpec

    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(
        cfg, params, n_slots=2, max_len=32,
        session=PlanSession.from_spec(PlanSpec(strategy="greedy_by_size")),
    )
    assert engine.memory_report.activation_plan.strategy == "greedy_by_size"


def test_engine_memory_report():
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32)
    rep = engine.memory_report
    plan = rep.activation_plan
    assert plan.total_size <= plan.naive_size
    assert plan.total_size >= plan.lower_bound
    # on this tiny config the plan should be essentially optimal AND a
    # real reduction vs naive co-residency
    assert plan.fraction_of_lower_bound <= 1.05, plan.summary()
    assert plan.reduction_vs_naive > 1.25, plan.summary()
    assert rep.cache_bytes_per_slot > 0
    assert "MiB" in rep.summary()
    # the unified report: the cross-step half is always planned, its slot
    # regions cover the measured per-slot cache bytes, and the engine's
    # state layout is a valid arena view over it
    assert rep.state_plan is not None
    assert rep.state_plan.n_slots == 2
    assert rep.state_plan.bytes_per_slot >= rep.cache_bytes_per_slot
    assert rep.unified_total_bytes == (
        plan.total_size + rep.state_plan.total_size
    )
    assert "unified footprint" in rep.summary()
    engine.state_layout.validate()
    assert engine.state_layout.total_size == rep.state_plan.total_size
    assert engine.unified_plan.activation is plan
    assert engine.unified_plan.state is rep.state_plan


def test_host_loop_retires_on_eos():
    """Regression (bugfix): the host loop never retired a request on EOS
    — only the max_new/max_len budgets ended it. With ``eos_id`` set, the
    request must stop at the FIRST emission of that token."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab, size=4).astype(np.int32)

    ref_engine = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    ref_engine.submit(prompt, max_new_tokens=10)
    ref = list(ref_engine.run_until_done()[0].tokens)
    assert len(ref) == 10, "no eos_id: the full budget is served"

    eos = ref[2]
    engine = InferenceEngine(cfg, params, n_slots=1, max_len=64,
                             eos_id=int(eos))
    engine.submit(prompt, max_new_tokens=10)
    got = list(engine.run_until_done()[0].tokens)
    assert got == ref[: ref.index(eos) + 1], (
        "request must retire at the first EOS emission, inclusive"
    )


def test_run_until_done_surfaces_exhausted_waves():
    """Regression (bugfix): ``run_until_done(max_waves=...)`` silently
    returned partial results. It must warn (or raise with the flag) and
    surface the unfinished requests."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab, size=4).astype(np.int32)

    engine = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    engine.submit(prompt, max_new_tokens=8)
    engine.submit(prompt, max_new_tokens=8)  # queued behind the one slot
    with pytest.warns(RuntimeWarning, match="exhausted max_waves"):
        done = engine.run_until_done(max_waves=5)
    unfinished = engine.unfinished_requests()
    assert len(done) + len(unfinished) == 2
    assert len(unfinished) >= 1

    from repro.runtime.engine import WavesExhaustedError

    engine2 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    engine2.submit(prompt, max_new_tokens=8)
    engine2.submit(prompt, max_new_tokens=8)
    with pytest.raises(WavesExhaustedError) as ei:
        engine2.run_until_done(max_waves=5, raise_on_exhausted=True)
    assert len(ei.value.unfinished) >= 1

    # a sufficient budget completes silently, with nothing left over
    engine3 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    engine3.submit(prompt, max_new_tokens=8)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        done = engine3.run_until_done()
    assert len(done) == 1 and not engine3.unfinished_requests()


def _float32_softmax(row):
    # the pre-fix implementation: float32 throughout, no renormalization
    x = row - row.max()
    e = np.exp(x)
    return e / e.sum()


def test_sample_probabilities_survive_generator_choice_tolerance():
    """Regression (bugfix): the float32 ``_softmax`` produced probability
    vectors whose float64 sum drifts past ``Generator.choice``'s strict
    tolerance (~1.5e-8) and raised "probabilities do not sum to 1". The
    fixed path computes in float64 and renormalizes explicitly."""
    cfg = get_reduced("qwen3-0.6b")
    rng = np.random.default_rng(0)
    bad = None
    for _ in range(5000):
        row = rng.normal(0, 4.0, cfg.vocab).astype(np.float32)
        p32 = _float32_softmax(row)
        if abs(float(p32.astype(np.float64).sum()) - 1.0) > 3e-7:
            bad = row
            break
    assert bad is not None, "hunt failed to produce a drifted row"

    # the old path trips numpy's strict float64 tolerance
    with pytest.raises(ValueError, match="[Pp]robabilities"):
        np.random.default_rng(0).choice(
            bad.size, p=_float32_softmax(bad).astype(np.float64)
        )

    from repro.runtime import sampling

    p = sampling.softmax(bad)
    assert p.dtype == np.float64
    assert abs(float(p.sum()) - 1.0) <= 1.5e-8
    np.random.default_rng(0).choice(bad.size, p=p)  # accepted

    t = sampling.host_probs(bad, temperature=0.8, top_k=50)
    assert t.dtype == np.float64
    np.random.default_rng(0).choice(bad.size, p=t)  # accepted

    # and the engine's sampling path draws from the same row
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=1, max_len=32,
                             greedy=False, sample_seed=0)
    tok = engine._sample_token(bad)
    assert 0 <= tok < cfg.vocab
