"""End-to-end tests for the compile→artifact→serve pipeline.

Covers the serving side of the unified planning API:
* ``launch/serve.py`` — end-to-end smoke on a reduced config (submit →
  run_until_done → token counts + slot-reuse audit) plus bucket
  auto-selection against a multi-bucket manifest;
* the plan-artifact path: engine construction from a v2 ``PlanBundle``
  (through ``PlanSession``) must perform NO jaxpr trace, NO planner call,
  and NO cross-step state layout (asserted via the instrumentation
  counters — both halves ship in the bundle), must produce a
  byte-identical ``MemoryPlan`` to the plan-at-construction path, and
  must degrade gracefully (one-line warning, plan-at-construction
  fallback) on fingerprint mismatch, a corrupt artifact, or a v1 bundle
  read by this v2 engine;
* the deprecated plan-source kwargs, which must keep working behind a
  ``DeprecationWarning``.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.analysis import counters
from repro.configs.base import get_reduced
from repro.core import plan_io
from repro.core.artifact import bucket_key, bundle_to_obj
from repro.core.unified import PlanSession
from repro.launch import serve
from repro.launch.compile import compile_and_publish
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine

ARCH = "qwen3-0.6b"
N_SLOTS, MAX_LEN = 2, 48


def _counters():
    # the no-work-at-serving-time discipline, via the analysis registry
    return counters.snapshot(("trace_calls", "plan_calls", "state_plan_calls"))


@pytest.fixture(scope="module")
def cfg():
    return get_reduced(ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return Model.for_config(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def bundle_dir(cfg, tmp_path_factory):
    d = tmp_path_factory.mktemp("bundles")
    compile_and_publish(
        cfg, d, n_slots=N_SLOTS, max_len=MAX_LEN, command="pytest"
    )
    return d


# ----------------------------------------------------------- serve driver


def test_serve_end_to_end_smoke():
    stats = serve.run([
        "--arch", ARCH, "--requests", "5", "--prompt-len", "4",
        "--max-new", "4", "--slots", "2", "--max-len", "48",
    ])
    assert stats["requests"] == 5
    assert stats["tokens"] == 5 * 4
    assert all(len(t) == 4 for t in stats["tokens_per_request"].values())
    assert stats["plan_source"] in ("planned", "cache")
    assert stats["cold_start_s"] > 0
    # unified accounting is part of the driver's report now
    assert stats["state_total_bytes"] > 0
    assert stats["unified_total_bytes"] == (
        stats["plan_total_bytes"] + stats["state_total_bytes"]
    )
    # slot-reuse audit: 5 requests over 2 slots must reuse slots, and no
    # two requests may overlap on one slot (the §4 invariant). serve.run
    # itself audits via shared_objects.from_slot_log (raises on overlap).
    log = stats["slot_log"]
    assert len(log) == 5
    by_slot: dict[int, list[tuple[int, int]]] = {}
    for slot, first, last, _rid in log:
        by_slot.setdefault(slot, []).append((first, last))
    assert any(len(v) > 1 for v in by_slot.values())


def test_serve_from_bundle_dir(bundle_dir):
    stats = serve.run([
        "--arch", ARCH, "--requests", "3", "--prompt-len", "4",
        "--max-new", "3", "--slots", str(N_SLOTS), "--max-len", str(MAX_LEN),
        "--plan-bundle", str(bundle_dir), "--compare-cold-start",
    ])
    assert stats["plan_source"] == "bundle"
    assert stats["bundle_warning"] is None
    assert stats["tokens"] == 3 * 3
    assert stats["cold_start_noartifact_s"] is not None
    assert stats["effective_max_len"] == MAX_LEN


def test_serve_auto_selects_nearest_bucket(cfg, tmp_path):
    """Acceptance: a multi-bucket manifest serves a request whose max_len
    has NO exact compiled match from the nearest compiled bucket — with
    zero traces, zero planner calls, and zero state layouts."""
    for max_len in (64, 128):
        compile_and_publish(
            cfg, tmp_path, n_slots=N_SLOTS, max_len=max_len, command="pytest"
        )
    before = _counters()
    stats = serve.run([
        "--arch", ARCH, "--requests", "2", "--prompt-len", "3",
        "--max-new", "2", "--slots", str(N_SLOTS), "--max-len", "96",
        "--plan-bundle", str(tmp_path),
    ])
    assert _counters() == before, (
        "bucket auto-selection traced/planned/laid out state"
    )
    assert stats["plan_source"] == "bundle"
    assert stats["requested_max_len"] == 96
    assert stats["effective_max_len"] == 128  # nearest compiled >= 96
    assert stats["tokens"] == 2 * 2
    # --exact-bucket turns selection off: miss -> fallback with the
    # readable bucket listing
    stats = serve.run([
        "--arch", ARCH, "--requests", "1", "--prompt-len", "3",
        "--max-new", "2", "--slots", str(N_SLOTS), "--max-len", "96",
        "--plan-bundle", str(tmp_path), "--exact-bucket",
    ])
    assert stats["plan_source"] in ("planned", "cache")
    assert "compiled buckets" in stats["bundle_warning"]


def test_serve_auto_selects_bigger_slot_pool(cfg, tmp_path):
    """Satellite: a fleet compiled only at slots=4 serves a slots=2
    request from the bigger pool (slots are the §4 shared objects — a
    wider pool is admissible, just wasteful) with zero traces/plans/state
    layouts; the engine reports the effective pool size."""
    compile_and_publish(cfg, tmp_path, n_slots=4, max_len=MAX_LEN,
                        command="pytest")
    before = _counters()
    stats = serve.run([
        "--arch", ARCH, "--requests", "3", "--prompt-len", "3",
        "--max-new", "2", "--slots", "2", "--max-len", str(MAX_LEN),
        "--plan-bundle", str(tmp_path),
    ])
    assert _counters() == before
    assert stats["plan_source"] == "bundle"
    assert stats["requested_slots"] == 2
    assert stats["effective_slots"] == 4
    assert stats["tokens"] == 3 * 2
    # one state allocation sized by the SERVED bucket's plan
    assert stats["state_live_bytes"] == stats["state_planned_bytes"]
    # --exact-bucket still disables the substitution
    stats = serve.run([
        "--arch", ARCH, "--requests", "1", "--prompt-len", "3",
        "--max-new", "2", "--slots", "2", "--max-len", str(MAX_LEN),
        "--plan-bundle", str(tmp_path), "--exact-bucket",
    ])
    assert stats["plan_source"] in ("planned", "cache")
    assert stats["effective_slots"] == 2


def test_serve_compile_first(tmp_path):
    out = tmp_path / "artifacts"
    stats = serve.run([
        "--arch", ARCH, "--requests", "2", "--prompt-len", "3",
        "--max-new", "2", "--slots", "2", "--max-len", "32",
        "--plan-bundle", str(out), "--compile-first",
    ])
    assert stats["plan_source"] == "bundle"
    assert (out / "manifest.json").exists()


# ------------------------------------------------------ artifact serving


def test_engine_from_bundle_no_trace_no_plan_no_state_layout(
    cfg, params, bundle_dir
):
    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls"
    ) as cap:
        engine = InferenceEngine(
            cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            session=PlanSession.from_manifest(bundle_dir),
        )
    assert cap.delta("trace_calls") == 0, "bundle path traced a jaxpr"
    assert cap.delta("plan_calls") == 0, "bundle path invoked the planner"
    assert cap.delta("state_plan_calls") == 0, (
        "bundle path laid out the cross-step state"
    )
    rep = engine.memory_report
    assert rep.plan_source == "bundle"
    assert rep.bundle_warning is None
    assert "precompiled bundle" in rep.summary()
    assert engine.plan_bundle is not None
    # BOTH halves came from the artifact
    assert rep.state_plan is not None
    assert rep.state_plan == engine.plan_bundle.state_plan
    assert engine.unified_plan.total_size == engine.plan_bundle.total_size
    # the activation layout comes straight from the stored offsets, and
    # the state layout from the stored slot/KV plan
    engine.activation_layout.validate()
    assert engine.activation_layout.total_size == rep.activation_plan.total_size
    assert engine.activation_layout.offsets == dict(
        engine.plan_bundle.plan.offsets
    )
    engine.state_layout.validate()
    assert engine.state_layout.total_size == rep.state_plan.total_size


def test_bundle_plan_byte_identical_to_construction_plan(cfg, params, bundle_dir):
    eng_b = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(bundle_dir),
    )
    eng_p = InferenceEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN)
    a = plan_io.plan_to_obj(eng_b.memory_report.activation_plan)
    b = plan_io.plan_to_obj(eng_p.memory_report.activation_plan)
    # wall time is measurement, not plan content
    a["plan_wall_s"] = b["plan_wall_s"] = 0.0
    ja = json.dumps(a, sort_keys=True, separators=(",", ":"))
    jb = json.dumps(b, sort_keys=True, separators=(",", ":"))
    assert ja == jb
    # the engine-side state layout matches the bundled one too
    from repro.core.unified import state_plan_to_obj

    assert state_plan_to_obj(eng_b.memory_report.state_plan) == (
        state_plan_to_obj(eng_p.memory_report.state_plan)
    )


def test_bundle_engine_serves_identical_tokens(cfg, params, bundle_dir):
    engines = [
        InferenceEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                        session=PlanSession.from_manifest(bundle_dir)),
        InferenceEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN),
    ]
    outs = []
    for eng in engines:
        rng = np.random.default_rng(7)
        for _ in range(3):
            eng.submit(rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                       max_new_tokens=3)
        done = eng.run_until_done()
        outs.append({r.request_id: r.tokens for r in done})
    assert outs[0] == outs[1]


def test_fingerprint_mismatch_falls_back_with_warning(cfg, params, bundle_dir):
    """An exact-bucket session must not serve a bundle whose fingerprint
    disagrees with the requested bucket; the engine plans at construction
    and says why in one line."""
    from repro.core.artifact import BundleManifest

    # grab the (valid) bundle and re-publish it under the bucket the engine
    # will look up for max_len=32 — fingerprint still says max_len=48
    man = BundleManifest(bundle_dir)
    good = man.lookup(bucket_key(cfg, n_slots=N_SLOTS, max_len=MAX_LEN))
    wrong_key = bucket_key(cfg, n_slots=N_SLOTS, max_len=32)
    man.publish(wrong_key, good)
    traces0 = counters.read("trace_calls")
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=32,
        session=PlanSession.from_manifest(bundle_dir, nearest=False),
    )
    rep = engine.memory_report
    assert rep.plan_source in ("planned", "cache")
    assert rep.bundle_warning is not None
    assert "fingerprint mismatch" in rep.bundle_warning
    assert "WARNING" in rep.summary()
    assert counters.read("trace_calls") > traces0  # fallback really replanned
    # and the engine still serves
    engine.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    assert len(engine.run_until_done()) == 1

    # the SAME situation with auto-selection on is admissible: the len=48
    # bundle (a self-consistent longer bucket) serves the len=32 request
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=32,
        session=PlanSession.from_manifest(bundle_dir),
    )
    assert engine.memory_report.plan_source == "bundle"
    assert engine.max_len == MAX_LEN


def test_missing_and_corrupt_bundles_fall_back(cfg, params, tmp_path):
    # missing bucket in an empty manifest dir — the warning lists what
    # exists (here: nothing)
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(tmp_path),
    )
    assert engine.memory_report.plan_source in ("planned", "cache")
    assert "unusable" in engine.memory_report.bundle_warning
    assert "manifest is empty" in engine.memory_report.bundle_warning
    # corrupt single-file bundles: garbage, valid-JSON-wrong-shape — all
    # must degrade to plan-at-construction, never crash serving
    for name, text in (("bad.json", "{not json"),
                       ("list.json", "[1, 2, 3]"),
                       ("shallow.json", '{"format_version": 2}')):
        bad = tmp_path / name
        bad.write_text(text)
        engine = InferenceEngine(
            cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            session=PlanSession.from_bundle(bad),
        )
        assert engine.memory_report.bundle_warning is not None, name
        assert engine.memory_report.plan_source in ("planned", "cache")


def test_v1_bundle_on_v2_engine_falls_back(
    cfg, params, bundle_dir, tmp_path, monkeypatch
):
    """Satellite: a v1 document loads through the shim (DeprecationWarning)
    but its fingerprint hashed schema v1 — a current engine must refuse it
    and plan at construction, preserving the fallback semantics."""
    from repro.core import artifact
    from repro.core.artifact import BundleManifest

    good = BundleManifest(bundle_dir).lookup(
        bucket_key(cfg, n_slots=N_SLOTS, max_len=MAX_LEN)
    )
    with monkeypatch.context() as m:
        # what decode_fingerprint produced when this build wrote v1 (the
        # fingerprint schema rolls independently of the bundle format, so
        # v2 documents keep matching a v3 engine — only the v1-era hash
        # is stale)
        m.setattr(artifact, "FINGERPRINT_SCHEMA_VERSION", 1)
        v1_fp = artifact.decode_fingerprint(
            cfg, n_slots=N_SLOTS, max_len=MAX_LEN
        )
    obj = bundle_to_obj(good)
    obj["format_version"] = 1
    obj["fingerprint"] = v1_fp
    for key in ("state_plan", "n_layers", "d_model"):
        del obj[key]
    f = tmp_path / "v1.json"
    f.write_text(json.dumps(obj, sort_keys=True, separators=(",", ":")))

    with pytest.deprecated_call(match="format v1"):
        engine = InferenceEngine(
            cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            session=PlanSession.from_bundle(f),
        )
    rep = engine.memory_report
    assert rep.plan_source in ("planned", "cache")
    assert "fingerprint mismatch" in rep.bundle_warning
    # the fallback still produced a full unified plan
    assert rep.state_plan is not None
    engine.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    assert len(engine.run_until_done()) == 1


def test_verify_bundle_checks_graph_fingerprint(cfg, params, bundle_dir, tmp_path):
    """The config fingerprint cannot see model-code changes;
    verify_graph=True trades the zero-trace cold start for a structural
    check of the stored graph fingerprint against a fresh trace."""
    from repro.core.artifact import BundleManifest, save_bundle

    good = BundleManifest(bundle_dir).lookup(
        bucket_key(cfg, n_slots=N_SLOTS, max_len=MAX_LEN)
    )
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_bundle(good, verify_graph=True),
    )
    assert engine.memory_report.plan_source == "bundle"

    tampered = dataclasses.replace(good, graph_fingerprint="0" * 64)
    f = tmp_path / "tampered.json"
    save_bundle(tampered, f)
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_bundle(f, verify_graph=True),
    )
    rep = engine.memory_report
    assert rep.plan_source in ("planned", "cache")
    assert "graph fingerprint mismatch" in rep.bundle_warning


def test_bundle_carries_xla_temp_measurement(cfg, params, bundle_dir):
    """compile.py measures XLA's temp allocation offline so bundle-served
    reports keep the planned-vs-XLA validation line."""
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(bundle_dir),
    )
    prov = engine.plan_bundle.provenance
    assert "xla_temp_bytes" in prov
    assert engine.memory_report.xla_temp_bytes == prov["xla_temp_bytes"]


def test_searched_bundle_is_served_and_never_worse(cfg, params, tmp_path):
    res = compile_and_publish(
        cfg, tmp_path, n_slots=N_SLOTS, max_len=MAX_LEN,
        search=True, search_iters=60, fusion_rounds=10,
    )
    assert res.bundle.plan.total_size <= res.greedy_plan.total_size
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(tmp_path),
    )
    rep = engine.memory_report
    assert rep.plan_source == "bundle"
    assert rep.activation_plan.total_size == res.bundle.plan.total_size
    prov = engine.plan_bundle.provenance
    assert prov["searched_total_bytes"] <= prov["greedy_total_bytes"]
    # searched plans still serve correct tokens
    engine.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    done = engine.run_until_done()
    assert len(done) == 1 and len(done[0].tokens) == 3
