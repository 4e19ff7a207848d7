"""Paged state subsystem tests: page-granular planning + paged decode.

The discipline mirrors the residency and scan-block differentials: the
symmetric (whole-slot-region) backend is the oracle, and the paged
backend — per-slot page tables over a fixed-page pool, allocate on
admission, free on retirement — must be BYTE-identical to it: same
tokens per request, same slot log, and every cache leaf bitwise-equal
after the run. On top of that the paged path proves its own economics
(live pool bytes track live tokens, not ``n_slots * slot_stride``) and
its own honesty (page audit via ``from_page_log``, refusal instead of
corruption when the pool runs dry, counters intact when serving a paged
bucket from a v3 bundle).
"""

import json

import jax
import numpy as np
import pytest

from repro.analysis import counters, soundness
from repro.configs.base import get_reduced
from repro.core.shared_objects import from_page_log
from repro.core.unified import (
    PagedStatePlan,
    StateRecord,
    detect_state_axes,
    plan_paged_state,
    plan_state,
    state_plan_from_obj,
    state_plan_to_obj,
    state_records_from_pytree,
)
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine
from repro.runtime.paging import PagedOutOfPagesError

ARCHS = ["qwen3-0.6b", "mamba2-2.7b", "zamba2-7b"]


def _params(cfg):
    return Model.for_config(cfg).init(jax.random.PRNGKey(0))


def _prompts(cfg, sizes=(4, 6, 3, 5, 4)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for n in sizes]


def _run(cfg, params, prompts, *, max_new=6, n_slots=2, max_len=64, **kw):
    engine = InferenceEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                             **kw)
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    done = engine.run_until_done()
    tokens = {r.request_id: list(r.tokens) for r in done}
    return engine, tokens


def _assert_byte_identical(sym, paged):
    for a, b in zip(jax.tree_util.tree_leaves(sym.caches),
                    jax.tree_util.tree_leaves(paged.caches)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ----------------------------------------------------------- plan level


def _toy_records(n_slots=2):
    # kv-like leaf: token axis 1 of 16 rows x 32 B; ssm-like leaf:
    # length-independent (no token axis)
    return [
        StateRecord(path="kv", shape=(n_slots, 16, 8), dtype="float32",
                    nbytes=n_slots * 16 * 8 * 4),
        StateRecord(path="ssm", shape=(n_slots, 24), dtype="float32",
                    nbytes=n_slots * 24 * 4),
    ], {"kv": (0, 1), "ssm": (0, None)}


def test_plan_paged_state_geometry():
    records, axes = _toy_records()
    base = plan_state(records, n_slots=2, max_len=16)
    for page in (64, 100):  # divisor and non-divisor of the stride
        sp = plan_paged_state(records, n_slots=2, max_len=16,
                              page_size=page, axes=axes)
        assert isinstance(sp, PagedStatePlan)
        # logical layout unchanged: the §4 objective the symmetric
        # certifiers reason about
        assert sp.total_size == base.total_size
        assert sp.slot_stride == base.slot_stride
        assert sp.pages_per_slot == -(-base.slot_stride // page)
        assert sp.n_pages_pool == 2 * sp.pages_per_slot  # default pool
        assert sp.phys_total_size == (sp.n_pages_pool + 1) * page
        # pool offsets are a permutation of physical pages 1..n (0 is
        # the reserved null page)
        assert sorted(o // page for o in sp.page_offsets) == \
            list(range(1, sp.n_pages_pool + 1))
        assert not soundness.certify_state_plan(sp), "pristine must be clean"


def test_pages_needed_tracks_live_tokens():
    records, axes = _toy_records()
    sp = plan_paged_state(records, n_slots=2, max_len=16, page_size=64,
                          axes=axes)
    all_pages = set(range(sp.pages_per_slot))
    prev: set = set()
    for length in (0, 1, 4, 8, 16):
        need = set(sp.pages_needed(length))
        assert prev <= need <= all_pages, length
        prev = need
    # the ssm leaf is fully live even at length 0
    assert sp.pages_needed(0), "length-independent leaves stay mapped"
    # short requests touch a strict subset of the slot's pages
    assert sp.live_bytes(1) < sp.pages_per_slot * sp.page_size
    assert set(sp.pages_needed(sp.max_len)) <= all_pages


def test_paged_plan_serialization_round_trip():
    records, axes = _toy_records()
    sp = plan_paged_state(records, n_slots=2, max_len=16, page_size=100,
                          axes=axes)
    rt = state_plan_from_obj(state_plan_to_obj(sp))
    assert isinstance(rt, PagedStatePlan)
    assert rt == sp
    # symmetric plans keep round-tripping to the symmetric class
    sym = state_plan_from_obj(state_plan_to_obj(
        plan_state(records, n_slots=2, max_len=16)))
    assert not isinstance(sym, PagedStatePlan)


@pytest.mark.parametrize("arch", ARCHS)
def test_detect_state_axes_every_leaf_has_a_slot_axis(arch):
    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    axes = detect_state_axes(model.init_cache, n_slots=2, max_len=32)
    assert axes
    caches = jax.eval_shape(lambda: model.init_cache(2, 32))
    leaves, _ = jax.tree_util.tree_flatten_with_path(caches)
    for path, leaf in leaves:
        slot_ax, tok_ax = axes[jax.tree_util.keystr(path)]
        assert leaf.shape[slot_ax] == 2
        if tok_ax is not None:
            assert leaf.shape[tok_ax] == 32


# -------------------------------------------------- byte-identity oracle


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_byte_identical_to_symmetric(arch):
    """The tentpole differential: paged decode (page tables, pool
    gather/scatter, allocate-on-admit/free-on-retire with slot reuse)
    against the symmetric backend — tokens, slot log, and every cache
    leaf bitwise, on both the host loop and the scan-block path."""
    cfg = get_reduced(arch)
    params = _params(cfg)
    prompts = _prompts(cfg)
    sym, sym_tokens = _run(cfg, params, prompts)
    paged, paged_tokens = _run(cfg, params, prompts, page_size=1024)
    assert paged.state.pages is not None and sym.state.pages is None
    assert paged_tokens == sym_tokens
    assert [tuple(x) for x in paged.slot_log] == \
        [tuple(x) for x in sym.slot_log]
    _assert_byte_identical(sym, paged)

    blk_sym, blk_sym_tokens = _run(cfg, params, prompts, block_size=4)
    blk_paged, blk_paged_tokens = _run(cfg, params, prompts, block_size=4,
                                       page_size=1024)
    assert blk_paged_tokens == blk_sym_tokens == sym_tokens
    _assert_byte_identical(blk_sym, blk_paged)


def test_paged_decode_non_divisor_page_size():
    """Page sizes that do not divide the slot stride leave a partial
    tail page per slot; the unpack/pack round trip must still be exact."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg)
    _, ref = _run(cfg, params, prompts)
    for page in (1000, 4096):
        paged, got = _run(cfg, params, prompts, page_size=page)
        assert got == ref, f"page_size={page} diverged"
        assert paged.state.pages.live == 0, "drained engine frees all pages"


def test_paged_seeded_sampling_matches_symmetric():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg, sizes=(4, 5))
    kw = dict(greedy=False, temperature=0.9, top_k=20, max_new=8,
              sample_seed=7)
    for extra in (dict(), dict(block_size=4)):
        sym, a = _run(cfg, params, prompts, **kw, **extra)
        paged, b = _run(cfg, params, prompts, page_size=1024, **kw, **extra)
        assert a == b, f"sampled trajectory diverged under paging ({extra})"
        _assert_byte_identical(sym, paged)


def test_page_size_must_hold_whole_elements():
    """The pool is one row of cache elements per page, so a page size
    that splits an element is refused when the backend binds."""
    from repro.runtime.paging import PagedStateResidency

    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    caches = jax.eval_shape(lambda: model.init_cache(2, 16))
    sp = plan_paged_state(
        state_records_from_pytree(caches, n_slots=2), n_slots=2,
        max_len=16, page_size=1002,
        axes=detect_state_axes(model.init_cache, n_slots=2, max_len=16),
    )
    with pytest.raises(ValueError, match="whole number"):
        PagedStateResidency(sp, caches, n_slots=2)


def test_slot_reuse_frees_and_recycles_pages():
    """Retirement returns a slot's pages to the pool; later admissions
    reuse them. The page log is a §4 shared-objects assignment one level
    below the slot log — ``from_page_log`` raises if any pool page
    served two requests at overlapping waves."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    engine, tokens = _run(cfg, params, _prompts(cfg), page_size=1024)
    assert len(tokens) == 5 and engine.n_slots == 2  # forced slot reuse
    log = engine.page_log
    assert log and all(fin >= adm for _, adm, fin, _ in log)
    sp = engine.memory_report.state_plan
    audit = from_page_log(log, state_plan=sp)
    assert len(audit.assignment) == len(log)
    by_page: dict = {}
    for page, _, _, rid in log:
        by_page.setdefault(page, set()).add(rid)
    assert any(len(rids) > 1 for rids in by_page.values()), \
        "no physical page was ever recycled across requests"
    assert engine.state.pages.live == 0
    assert engine.state.pages.peak > 0


def test_from_page_log_rejects_double_assignment_and_null_page():
    records, axes = _toy_records()
    sp = plan_paged_state(records, n_slots=2, max_len=16, page_size=64,
                          axes=axes)
    with pytest.raises(ValueError, match="null page"):
        from_page_log([(0, 0, 3, 0)], state_plan=sp)
    with pytest.raises(ValueError):
        from_page_log([(1, 0, 5, 0), (1, 4, 8, 1)], state_plan=sp)
    # disjoint residencies on one page are exactly what reuse looks like
    from_page_log([(1, 0, 3, 0), (1, 4, 8, 1)], state_plan=sp)


# ------------------------------------------------- pool economics/honesty


def test_live_paged_bytes_beat_symmetric_plan_at_low_fill():
    """The headline win: at <= 25% fill (1 of 4 slots, short request)
    the paged backend's live pool bytes are >= 3x smaller than the
    symmetric plan's always-allocated ``total_size``."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    engine, _ = _run(cfg, params, _prompts(cfg, sizes=(4,)), max_new=4,
                     n_slots=4, page_size=512)
    sp = engine.memory_report.state_plan
    peak = engine.state.pages.peak * sp.page_size
    assert peak > 0
    assert peak * 3 <= sp.total_size, (
        f"peak live {peak} B not 3x under symmetric {sp.total_size} B"
    )


def test_memory_report_honest_under_paging():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                             page_size=1024)
    rep0 = engine.memory_report
    assert rep0.state_pages_total == engine.state.pages.total
    assert rep0.state_pages_live == 0
    assert rep0.state_page_size == 1024
    assert rep0.cache_bytes_per_slot == 0, "no live pages, no cache bytes"
    assert "paged" in rep0.summary()

    for p in _prompts(cfg, sizes=(4, 6)):
        engine.submit(p, max_new_tokens=6)
    engine.step()
    rep = engine.memory_report
    assert rep.state_pages_live == engine.state.pages.live > 0
    assert rep.state_live_bytes == rep.state_pages_live * 1024
    # live-page bytes per ACTIVE slot, not the symmetric per-slot stride
    assert rep.cache_bytes_per_slot == rep.state_live_bytes // 2
    assert rep.cache_bytes_per_slot < rep.state_plan.bytes_per_slot
    engine.run_until_done()
    # symmetric engines keep the fields unset
    sym = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    assert sym.memory_report.state_pages_total is None
    assert sym.memory_report.state_page_size is None


# --------------------------------------------------------- pool pressure


def test_out_of_pages_refuses_without_corruption():
    """A pool sized for ~one slot serializes admissions: requests wait
    (head-of-line) instead of corrupting live slots, and every request
    still finishes with the unconstrained engine's exact tokens."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    # equal-length prompts -> every request needs the same page count
    prompts = _prompts(cfg, sizes=(4, 4, 4, 4))
    base, ref = _run(cfg, params, prompts, page_size=1024)
    sp = base.memory_report.state_plan
    need = len(sp.pages_needed(min(4 + 6, 64)))
    # one request always fits, two never do
    tight, got = _run(cfg, params, prompts, page_size=1024,
                      page_pool=2 * need - 1)
    assert got == ref, "pool pressure changed decoded tokens"
    assert tight.state.pages.peak <= 2 * need - 1
    slots_busy = [
        {s for s, a, f, _ in tight.slot_log if a <= w <= f}
        for w in range(tight._wave)
    ]
    assert all(len(s) <= 1 for s in slots_busy), "admissions not serialized"
    assert tight._wave > base._wave, "serialization must cost extra waves"
    from_page_log(tight.page_log, state_plan=tight.memory_report.state_plan)


def test_unfittable_request_raises_clear_error():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                             page_size=1024, page_pool=1)
    engine.submit(_prompts(cfg, sizes=(4,))[0], max_new_tokens=60)
    with pytest.raises(PagedOutOfPagesError, match="paged admission refused"):
        engine.run_until_done()
    e = PagedOutOfPagesError(pages_needed=7, pages_free=1, pages_live=3,
                             pages_total=4)
    assert "7 page(s)" in str(e) and "1 of" in str(e) and "4 pool" in str(e)


def test_unfinished_requests_under_pool_pressure():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    probe = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                            page_size=1024)
    per_slot = probe.memory_report.state_plan.pages_per_slot
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                             page_size=1024, page_pool=per_slot)
    for p in _prompts(cfg, sizes=(4, 5)):
        engine.submit(p, max_new_tokens=10)
    with pytest.warns(RuntimeWarning, match="exhausted"):
        engine.run_until_done(max_waves=4)
    assert len(engine.unfinished_requests()) >= 1
    assert engine.state.pages.live <= per_slot


# ------------------------------------------------------- artifact serving


def test_paged_bundle_serves_with_zero_work(tmp_path):
    """Zero-trace / zero-plan / zero-compile serving of a PAGED bucket
    from a v3 manifest: the page knobs join the serve fingerprint and
    bucket key, the AOT pack carries ``paged_*`` executables, and the
    engine pays no compiles serving them."""
    from repro.core.artifact import parse_bucket_key, serve_fingerprint
    from repro.core.unified import PlanSession
    from repro.launch.compile import compile_and_publish

    assert serve_fingerprint(page_size=1024) is not None
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    res = compile_and_publish(cfg, str(tmp_path), n_slots=2, max_len=64,
                              page_size=1024, measure_xla=False)
    assert isinstance(res.bundle.state_plan, PagedStatePlan)
    assert {"paged_decode", "paged_reset"} <= set(
        res.bundle.executables.entries
    )
    keys = list(json.loads(
        (tmp_path / "manifest.json").read_text())["buckets"])
    assert any(
        (parse_bucket_key(k) or {}).get("page_size") == 1024 for k in keys
    )

    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls", "compile_calls"
    ) as cap:
        engine, tokens = _run(
            cfg, params, _prompts(cfg, sizes=(4, 5)),
            session=PlanSession.from_manifest(str(tmp_path)),
            page_size=1024,
        )
    assert engine.memory_report.plan_source == "bundle", (
        engine.memory_report.bundle_warning
    )
    assert engine.state.pages is not None
    assert cap.delta("trace_calls") == 0
    assert cap.delta("plan_calls") == 0
    assert cap.delta("state_plan_calls") == 0
    assert cap.delta("compile_calls") == 0, "paged AOT pack was not served"
    assert len(tokens) == 2

    # a symmetric engine must NOT pick up the paged bucket
    sym = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                          session=PlanSession.from_manifest(str(tmp_path)))
    assert sym.memory_report.plan_source != "bundle"


def test_paged_meta_mismatch_is_linted(tmp_path):
    from repro.analysis import bundle_lint
    from repro.core.artifact import serve_fingerprint
    from repro.launch.compile import compile_decode_plan

    cfg = get_reduced("qwen3-0.6b")
    res = compile_decode_plan(cfg, n_slots=2, max_len=32, page_size=1024,
                              measure_xla=False, aot=False)
    sp = serve_fingerprint(page_size=1024)
    assert not [
        f for f in bundle_lint.lint_bundle(res.bundle, serve_params=sp)
        if f.severity == "error"
    ]
    # a serving context that disagrees on the page knob is flagged —
    # both a page-less context and a different page size
    for bad in (serve_fingerprint(block_size=8),
                serve_fingerprint(page_size=512)):
        findings = bundle_lint.lint_bundle(res.bundle, serve_params=bad)
        assert "paged-meta-mismatch" in {f.code for f in findings}, bad


def test_residency_off_falls_back_to_symmetric_with_warning():
    """Paging with residency off is refused: serving the symmetric pytree
    backend instead would silently drop the paging that was asked for.
    Residency off without paging still serves the pytree backend."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    with pytest.raises(ValueError, match="paged state requires"):
        InferenceEngine(cfg, params, n_slots=2, max_len=64,
                        page_size=1024, state_residency=False)
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=64,
                             state_residency=False)
    assert engine.state.pages is None
    engine.submit(_prompts(cfg, sizes=(4,))[0], max_new_tokens=4)
    assert len(engine.run_until_done()) == 1
