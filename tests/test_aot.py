"""AOT decode executables (PlanBundle v3): zero-compile serving.

The tentpole contract, pinned end-to-end:

* a v3 bundle serves its first token with ZERO XLA compiles — the
  ``COMPILE_CALLS`` counter, same discipline as the zero-trace /
  zero-plan asserts;
* decode outputs are byte-identical to the lazily-compiled path, for
  every addressing of the state (symmetric buffer, plain cache pytree,
  paged buffer) and on the scan-block path;
* a v2 document degrades to lazy compile (DeprecationWarning, plans
  still served from the bundle — the fingerprint schema rolls
  separately from the bundle format);
* a stale pack (platform / jax-version / payload-integrity mismatch)
  is refused with one RuntimeWarning and falls back to lazy compile —
  never a crash, and never a partial load;
* ``decode_lint.lint_executables`` passes a fresh pack and flags an
  undeserializable payload.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs.base import get_reduced
from repro.core.artifact import (
    BundleManifest,
    bucket_key,
    bundle_to_obj,
    expected_executable_entries,
    save_bundle,
)
from repro.core.unified import PlanSession
from repro.launch.compile import compile_and_publish
from repro.models.api import Model
from repro.runtime import residency
from repro.runtime.engine import InferenceEngine

N_SLOTS, MAX_LEN = 2, 32


@pytest.fixture(scope="module")
def cfg():
    return get_reduced("qwen3-0.6b")


@pytest.fixture(scope="module")
def params(cfg):
    return Model.for_config(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def bundle_dir(cfg, tmp_path_factory):
    d = tmp_path_factory.mktemp("aot_bundles")
    compile_and_publish(
        cfg, d, n_slots=N_SLOTS, max_len=MAX_LEN, measure_xla=False
    )
    return d


@pytest.fixture(scope="module")
def bundle(bundle_dir, cfg):
    return BundleManifest(bundle_dir).lookup(
        bucket_key(cfg, n_slots=N_SLOTS, max_len=MAX_LEN)
    )


def _serve(engine, max_new=3, n_requests=2):
    rng = np.random.default_rng(7)
    for _ in range(n_requests):
        engine.submit(
            rng.integers(0, 100, size=4).astype(np.int32),
            max_new_tokens=max_new,
        )
    return {r.request_id: list(r.tokens) for r in engine.run_until_done()}


def test_v3_bundle_serves_with_zero_compiles(cfg, params, bundle_dir):
    c0 = residency.COMPILE_CALLS
    engine = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(bundle_dir),
    )
    rep = engine.memory_report
    assert rep.plan_source == "bundle"
    assert rep.aot_warning is None
    assert rep.aot_executables == expected_executable_entries()
    assert "zero-compile" in rep.summary()
    tokens = _serve(engine)
    assert tokens and all(len(t) == 3 for t in tokens.values())
    assert residency.COMPILE_CALLS - c0 == 0


@pytest.mark.parametrize("addressing", ["resident", "pytree", "paged"])
def test_aot_tokens_byte_identical_to_lazy(
    cfg, params, bundle, bundle_dir, tmp_path, addressing
):
    """The AOT executables ARE the programs the engine would have jitted
    — same bundle with the pack stripped must emit the same bytes, for
    each addressing of the state (symmetric buffer, reference pytree,
    paged buffer)."""
    kw = dict(
        n_slots=N_SLOTS, max_len=MAX_LEN,
        state_residency=addressing != "pytree",
    )
    if addressing == "paged":
        kw["page_size"] = 1024
        bundle_dir = tmp_path / "paged"
        bundle = compile_and_publish(
            cfg, bundle_dir, n_slots=N_SLOTS, max_len=MAX_LEN,
            page_size=1024, measure_xla=False,
        ).bundle
    stripped = tmp_path / "lazy.json"
    save_bundle(dataclasses.replace(bundle, executables=None), stripped)
    aot = InferenceEngine(
        cfg, params, session=PlanSession.from_manifest(bundle_dir), **kw
    )
    assert f"{addressing}_decode" in aot.memory_report.aot_executables
    lazy = InferenceEngine(
        cfg, params, session=PlanSession.from_bundle(stripped), **kw
    )
    assert lazy.memory_report.plan_source == "bundle"
    assert lazy.memory_report.aot_executables == []
    assert _serve(aot) == _serve(lazy), (
        f"AOT tokens diverged from lazy ({addressing})"
    )


def test_aot_block_path_zero_compile_and_identical(cfg, params, tmp_path):
    """Full-K scan blocks run from the bundled block executable (zero
    compiles); tokens match the lazily-compiled block engine."""
    d = tmp_path / "blocks"
    compile_and_publish(
        cfg, d, n_slots=N_SLOTS, max_len=MAX_LEN, block_size=2,
        measure_xla=False,
    )
    c0 = residency.COMPILE_CALLS
    aot = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, block_size=2,
        session=PlanSession.from_manifest(d),
    )
    assert "resident_block_2" in aot.memory_report.aot_executables
    tokens = _serve(aot, max_new=4)  # multiple of K: full blocks only
    assert residency.COMPILE_CALLS - c0 == 0
    lazy = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, block_size=2
    )
    assert tokens == _serve(lazy, max_new=4)


def test_bundle_compiled_with_the_compile_cache_on_serves(
    cfg, params, bundle_dir, tmp_path
):
    """A bundle compiled twice with the persistent compile cache on (the
    second compile could read every decode program back from it) still
    serves, with zero compiles and the tokens of a bundle compiled with
    the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path / "xla"), True, 0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        for name in ("cold", "warm"):
            compile_and_publish(
                cfg, tmp_path / name, n_slots=N_SLOTS, max_len=MAX_LEN,
                measure_xla=False,
            )
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    c0 = residency.COMPILE_CALLS
    warm = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(tmp_path / "warm"),
    )
    assert warm.memory_report.aot_warning is None
    tokens = _serve(warm)
    assert residency.COMPILE_CALLS - c0 == 0
    ref = InferenceEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
        session=PlanSession.from_manifest(bundle_dir),
    )
    assert tokens == _serve(ref)


def test_v2_bundle_degrades_to_lazy_compile(cfg, params, bundle, tmp_path):
    """Satellite: a v2 document still serves its PLANS from the bundle —
    only the executables are missing, so the engine pays lazy compiles
    (and nothing else) behind one DeprecationWarning."""
    obj = bundle_to_obj(bundle)
    obj["format_version"] = 2
    obj.pop("executables", None)
    f = tmp_path / "v2.json"
    f.write_text(json.dumps(obj, sort_keys=True, separators=(",", ":")))
    c0 = residency.COMPILE_CALLS
    with pytest.deprecated_call(match="format v2"):
        engine = InferenceEngine(
            cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            session=PlanSession.from_bundle(f),
        )
    rep = engine.memory_report
    assert rep.plan_source == "bundle"  # fingerprint schema decoupled
    assert rep.aot_executables == []
    assert rep.aot_warning is None
    tokens = _serve(engine)
    assert tokens
    assert residency.COMPILE_CALLS - c0 >= 1  # the lazy decode compile


@pytest.mark.parametrize(
    "mutate, match",
    [
        (lambda p: dataclasses.replace(p, platform="notaplatform"),
         "platform"),
        (lambda p: dataclasses.replace(p, jax_version="0.0.0"), "jax"),
        (
            lambda p: dataclasses.replace(
                p,
                entries={
                    n: (
                        dataclasses.replace(e, sha256="0" * 64)
                        if n == sorted(p.entries)[0] else e
                    )
                    for n, e in p.entries.items()
                },
            ),
            "integrity",
        ),
    ],
    ids=["platform", "jax-version", "sha256"],
)
def test_stale_pack_refused_and_falls_back(
    cfg, params, bundle, tmp_path, mutate, match
):
    """A cross-platform / cross-jax / corrupted pack is refused whole —
    one RuntimeWarning, lazy compile, tokens still served."""
    f = tmp_path / "stale.json"
    save_bundle(
        dataclasses.replace(bundle, executables=mutate(bundle.executables)),
        f,
    )
    c0 = residency.COMPILE_CALLS
    with pytest.warns(RuntimeWarning, match=match):
        engine = InferenceEngine(
            cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            session=PlanSession.from_bundle(f),
        )
    rep = engine.memory_report
    assert rep.plan_source == "bundle"  # the plans are still good
    assert rep.aot_executables == []  # all-or-nothing: no partial load
    assert "falling back to lazy compile" in rep.aot_warning
    assert _serve(engine)
    assert residency.COMPILE_CALLS - c0 >= 1


def test_lint_executables_passes_fresh_and_flags_corrupt(bundle):
    from repro.analysis import decode_lint

    # warning-severity findings are backend noise (the CPU scatter loops
    # show up as whole-state-buffer copies); the publish gate blocks on
    # errors, so that is what a fresh pack must be free of
    fresh = decode_lint.lint_executables(bundle)
    assert [f for f in fresh if f.severity == "error"] == []
    name = sorted(bundle.executables.entries)[0]
    broken = dataclasses.replace(
        bundle,
        executables=dataclasses.replace(
            bundle.executables,
            entries={
                **bundle.executables.entries,
                name: dataclasses.replace(
                    bundle.executables.entries[name], payload=b"garbage"
                ),
            },
        ),
    )
    findings = decode_lint.lint_executables(broken)
    assert any(f.code == "executable-load-failed" for f in findings)
