"""Scan-block decode tests: the device-resident serving loop.

The discipline mirrors the residency differential tests: the single-wave
host loop is the oracle, and greedy block decode must be BYTE-identical
to it — same tokens per request, same slot log (admission/finish waves),
and every cache leaf bitwise-equal after the run. On-device sampling
must be reproducible under a fixed seed and — because per-slot PRNG keys
advance per emission, not per wave — invariant to the block size.
"""

import jax
import numpy as np
import pytest

from repro.analysis import counters
from repro.configs.base import get_reduced
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine


def _params(cfg):
    return Model.for_config(cfg).init(jax.random.PRNGKey(0))


def _run(cfg, params, prompts, *, max_new=6, n_slots=2, max_len=64, **kw):
    engine = InferenceEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                             **kw)
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    done = engine.run_until_done()
    tokens = {r.request_id: list(r.tokens) for r in done}
    return engine, tokens


def _prompts(cfg, sizes=(4, 6, 3, 5, 4)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for n in sizes]


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-2.7b", "zamba2-7b"])
def test_greedy_block_decode_byte_identical_to_host_loop(arch):
    """The tentpole differential: multi-wave scan decode (slot reuse,
    staggered admission, on-device stop detection) against the per-wave
    host loop — tokens, slot log, and every cache leaf bitwise."""
    cfg = get_reduced(arch)
    params = _params(cfg)
    prompts = _prompts(cfg)
    host, host_tokens = _run(cfg, params, prompts)
    block, block_tokens = _run(cfg, params, prompts, block_size=4)
    assert block_tokens == host_tokens
    assert [tuple(x) for x in block.slot_log] == \
        [tuple(x) for x in host.slot_log]
    for a, b in zip(jax.tree_util.tree_leaves(host.caches),
                    jax.tree_util.tree_leaves(block.caches)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_greedy_block_decode_identical_across_block_sizes():
    """Non-divisor block sizes (the block-length policy trims blocks to
    land predictable finishes on block ends) stay on the oracle's
    trajectory too."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg)
    _, ref = _run(cfg, params, prompts)
    for bs in (2, 3, 5, 16):
        _, got = _run(cfg, params, prompts, block_size=bs)
        assert got == ref, f"block_size={bs} diverged from the host loop"


def test_block_decode_works_with_residency_off():
    """The scan path serves every addressing: the reference pytree
    (residency off) serves the same tokens as the donated buffer."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg)
    on, on_tokens = _run(cfg, params, prompts, block_size=4)
    off, off_tokens = _run(cfg, params, prompts, block_size=4,
                           state_residency=False)
    assert on.state.residency and not off.state.residency
    assert on_tokens == off_tokens


def test_seeded_on_device_sampling_reproducible_and_block_invariant():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg, sizes=(4, 5))
    kw = dict(greedy=False, temperature=0.9, top_k=20, max_new=8)
    _, a = _run(cfg, params, prompts, block_size=4, sample_seed=7, **kw)
    _, b = _run(cfg, params, prompts, block_size=4, sample_seed=7, **kw)
    assert a == b, "same seed must reproduce the sampled trajectory"
    # keys advance per EMISSION, not per wave: regrouping waves into
    # different blocks must not change the sampled tokens
    _, c = _run(cfg, params, prompts, block_size=2, sample_seed=7, **kw)
    assert a == c, "sampled decode must be invariant to the block size"
    _, d = _run(cfg, params, prompts, block_size=4, sample_seed=8, **kw)
    assert a != d, "a different seed must change the trajectory"


def test_eos_stops_on_device_matching_host_oracle():
    """EOS detection inside the scan (the device half of satellite 1):
    both paths truncate at the first EOS emission, and agree."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg, sizes=(4,))
    _, ref = _run(cfg, params, prompts, max_new=10)
    ref_tokens = ref[0]
    eos = ref_tokens[2]
    expect = ref_tokens[: ref_tokens.index(eos) + 1]
    for bs in (1, 8):
        _, got = _run(cfg, params, prompts, max_new=10, eos_id=int(eos),
                      block_size=bs)
        assert got[0] == expect, f"block_size={bs}"


def test_host_syncs_one_per_scan_block():
    """The counter discipline (same as zero-trace/zero-plan): the block
    path synchronizes with the host EXACTLY once per scan block; the
    host loop pays one sync per wave."""
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    prompts = _prompts(cfg)

    with counters.capture("host_syncs") as cap:
        host, _ = _run(cfg, params, prompts)
    host_syncs = cap.delta("host_syncs")
    assert host_syncs == host._wave, "host loop: one sync per wave"

    with counters.capture("host_syncs") as cap:
        block, _ = _run(cfg, params, prompts, block_size=4)
    block_syncs = cap.delta("host_syncs")
    assert block_syncs == block.n_blocks, (
        f"{block_syncs} syncs over {block.n_blocks} blocks"
    )
    assert block_syncs < host_syncs
    assert block._wave == host._wave, "both modes serve the same waves"


def test_run_until_done_exhaust_warns_in_block_mode():
    cfg = get_reduced("qwen3-0.6b")
    params = _params(cfg)
    engine = InferenceEngine(cfg, params, n_slots=1, max_len=64,
                             block_size=4)
    p = _prompts(cfg, sizes=(4,))[0]
    engine.submit(p, max_new_tokens=10)
    engine.submit(p, max_new_tokens=10)
    with pytest.warns(RuntimeWarning, match="exhausted"):
        engine.run_until_done(max_waves=6)
    assert engine._wave <= 6, "block mode must respect the wave budget"
    assert len(engine.unfinished_requests()) >= 1


def test_block_size_and_sampling_join_the_decode_fingerprint(tmp_path):
    """Bundles stay self-invalidating across serving configurations: a
    default-compiled bundle is refused by a block-decode engine (fallback
    with a fingerprint warning), and a bundle compiled for the same
    block/sampling config is served."""
    from repro.core.artifact import decode_fingerprint, serve_fingerprint
    from repro.core.unified import PlanSession
    from repro.launch.compile import compile_and_publish

    assert serve_fingerprint() is None  # default host loop: unchanged hash
    assert serve_fingerprint(block_size=1, greedy=True) is None
    # greedy canonicalizes the sampling knobs away
    assert serve_fingerprint(temperature=0.5, top_k=10) is None
    sp = serve_fingerprint(block_size=8)
    assert sp is not None
    cfg = get_reduced("qwen3-0.6b")
    fp = decode_fingerprint(cfg, n_slots=2, max_len=64)
    assert fp != decode_fingerprint(cfg, n_slots=2, max_len=64,
                                    serve_params=sp)
    assert fp == decode_fingerprint(cfg, n_slots=2, max_len=64,
                                    serve_params=None)

    params = _params(cfg)
    default_dir = tmp_path / "default"
    compile_and_publish(cfg, str(default_dir), n_slots=2, max_len=64,
                        measure_xla=False)
    engine = InferenceEngine(
        cfg, params, n_slots=2, max_len=64, block_size=8,
        session=PlanSession.from_manifest(str(default_dir)),
    )
    assert engine.memory_report.plan_source != "bundle"
    assert "fingerprint mismatch" in (engine.memory_report.bundle_warning or "")

    block_dir = tmp_path / "block"
    compile_and_publish(cfg, str(block_dir), n_slots=2, max_len=64,
                        block_size=8, measure_xla=False)
    engine2 = InferenceEngine(
        cfg, params, n_slots=2, max_len=64, block_size=8,
        session=PlanSession.from_manifest(str(block_dir)),
    )
    assert engine2.memory_report.plan_source == "bundle", (
        engine2.memory_report.bundle_warning
    )
    # and it serves correctly off the bundle
    engine2.submit(_prompts(cfg, sizes=(4,))[0], max_new_tokens=8)
    done = engine2.run_until_done()
    assert len(done) == 1 and len(done[0].tokens) == 8
