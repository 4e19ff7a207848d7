"""State-residency tests: the planned layout IS the live layout.

The tentpole contract of the residency subsystem
(``runtime/residency.py``): with residency on (default), the engine's
whole cross-step state — per-slot KV caches + decode buffers — lives in
ONE device buffer of exactly ``StatePlan.total_size`` bytes, carved into
per-(slot, leaf) views by the plan's ``leaf_view_spec`` and
donate-threaded through the decode jit. Decode outputs must be
byte-identical to the XLA-allocated cache-pytree baseline
(``REPRO_STATE_RESIDENCY=off``) across architectures — attention,
SSM, and hybrid shared-attention caches all round-trip the arena.

Also covers the satellite failure modes: ``ArenaLayout`` materialization
from corrupt state plans (overlapping regions, offsets past the buffer)
and from v1 bundles (``state_plan=None``) raise clear errors.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_reduced
from repro.core.unified import (
    StateLeaf,
    plan_state,
    state_records_from_pytree,
)
from repro.models.api import Model
from repro.runtime.arena import Arena, ArenaLayout, DeviceArena
from repro.runtime.engine import InferenceEngine
from repro.runtime.residency import (
    PytreeAddressing,
    ResidentState,
    StateResidency,
    residency_enabled,
)
from repro.runtime.sampling import SamplingParams, TokenSampler

ARCHS = ["qwen3-0.6b", "mamba2-2.7b", "zamba2-7b"]


def _setup(arch: str, n_slots: int = 2, max_len: int = 32):
    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    caches = model.init_cache(n_slots, max_len)
    sp = plan_state(
        state_records_from_pytree(caches, n_slots=n_slots),
        n_slots=n_slots, max_len=max_len,
    )
    return cfg, model, params, caches, sp


# --------------------------------------------------------- leaf_view_spec


def test_leaf_view_spec_addresses_every_cell():
    """The leaf addressing API: dense ids, one cell per (slot, leaf), at
    exactly slot_stride*slot + leaf.offset, payload within the planned
    slot, everything inside the buffer."""
    _, _, _, caches, sp = _setup("qwen3-0.6b")
    views = sp.leaf_view_spec()
    assert len(views) == sp.n_slots * len(sp.leaves)
    for i, view in enumerate(views):
        leaf = sp.leaves[view.leaf_index]
        assert view.tensor_id == i  # dense: slot * n_leaves + leaf_index
        assert view.slot == i // len(sp.leaves)
        assert view.path == leaf.path
        assert view.offset == view.slot * sp.slot_stride + leaf.offset
        assert view.slot_nbytes == leaf.slot_nbytes
        assert 0 < view.used_nbytes <= view.slot_nbytes
        assert view.offset + view.slot_nbytes <= sp.total_size
    # the legacy tuple view is the same cells
    for view, (tid, slot, leaf, off) in zip(views, sp.flat_entries()):
        assert (view.tensor_id, view.slot, view.offset) == (tid, slot, off)
        assert leaf.path == view.path


def test_state_layout_cells_are_disjoint():
    _, _, _, _, sp = _setup("mamba2-2.7b")
    layout = ArenaLayout.from_state_plan(sp)
    layout.validate()
    layout.validate_disjoint()  # state is all live at once: no aliasing
    assert layout.total_size == sp.total_size


# ------------------------------------------------------------ DeviceArena


def test_device_arena_store_view_round_trip():
    _, _, _, _, sp = _setup("qwen3-0.6b")
    arena = DeviceArena(ArenaLayout.from_state_plan(sp), jnp.float32)
    buf = arena.allocate()
    assert buf.nbytes == sp.total_size
    assert buf.dtype == jnp.float32 and buf.ndim == 1
    view = sp.leaf_view_spec()[0]
    n = view.used_nbytes // 4
    value = jnp.arange(n, dtype=jnp.float32)
    start = view.offset // 4
    buf = buf.at[start : start + n].set(value)
    got = arena.view(buf, view.tensor_id, (n,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(value))
    # and other cells stayed zero
    other = sp.leaf_view_spec()[1]
    rest = arena.view(
        buf, other.tensor_id, (other.used_nbytes // 4,), jnp.float32
    )
    assert float(np.abs(np.asarray(rest)).sum()) == 0
    # one element dtype per arena: a byte view would need a bitcast
    with pytest.raises(ValueError, match="never bitcasts"):
        arena.view(buf, view.tensor_id, (view.used_nbytes,), jnp.uint8)


def test_device_arena_enforces_the_same_bounds_contract_as_arena():
    """The jax twin must reject oversized views exactly like the numpy
    arena — a too-large view would silently alias the next slot."""
    _, _, _, _, sp = _setup("qwen3-0.6b")
    layout = ArenaLayout.from_state_plan(sp)
    device, host = DeviceArena(layout, jnp.float32), Arena(layout)
    view = sp.leaf_view_spec()[0]
    too_big = view.slot_nbytes + 64
    with pytest.raises(ValueError, match="exceeds planned"):
        device.view(
            device.allocate(), view.tensor_id, (too_big // 4,), jnp.float32
        )
    with pytest.raises(ValueError, match="exceeds planned"):
        host.view(view.tensor_id, (too_big,), np.uint8)


# -------------------------------------------------- StateResidency binding


@pytest.mark.parametrize("arch", ARCHS)
def test_pack_unpack_round_trips_the_cache_pytree(arch):
    cfg, model, params, caches, sp = _setup(arch)
    res = StateResidency(sp, caches, n_slots=2)
    buf = res.init_buffer(caches)
    assert buf.nbytes == sp.total_size
    rebuilt = res.unpack(buf)
    for (p1, a), (p2, b) in zip(
        jax.tree_util.tree_flatten_with_path(caches)[0],
        jax.tree_util.tree_flatten_with_path(rebuilt)[0],
    ):
        assert p1 == p2
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # packing nonzero caches round-trips bytes exactly too
    nonzero = jax.tree_util.tree_map(
        lambda x: (jnp.arange(x.size, dtype=jnp.float32) % 7 + 1)
        .reshape(x.shape).astype(x.dtype),
        caches,
    )
    buf2 = res.init_buffer(nonzero)
    for (_, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(nonzero)[0],
        jax.tree_util.tree_flatten_with_path(res.unpack(buf2))[0],
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_residency_rejects_foreign_plans_and_templates():
    _, model, _, caches, sp = _setup("qwen3-0.6b")
    # slot-count mismatch
    with pytest.raises(ValueError, match="slots"):
        StateResidency(sp, caches, n_slots=4)
    # a plan for a different model's cache pytree
    _, _, _, other_caches, other_sp = _setup("mamba2-2.7b")
    with pytest.raises(ValueError, match="does not cover"):
        StateResidency(other_sp, caches, n_slots=2)
    # dtype drift between plan and cache
    bad = dataclasses.replace(
        sp,
        leaves=[dataclasses.replace(l, dtype="float64") for l in sp.leaves],
    )
    with pytest.raises(ValueError, match="dtype"):
        StateResidency(bad, caches, n_slots=2)


def test_residency_refuses_mixed_dtype_caches():
    """One buffer holds one element dtype: the device views are slices
    and reshapes, never bitcasts, so a cache pytree that mixes dtypes is
    refused at binding time."""
    caches = {
        "k": jnp.zeros((2, 8), jnp.float32),
        "v": jnp.zeros((2, 8), jnp.bfloat16),
    }
    sp = plan_state(
        state_records_from_pytree(caches, n_slots=2), n_slots=2, max_len=8
    )
    with pytest.raises(ValueError, match="mixes"):
        StateResidency(sp, caches, n_slots=2)


# --------------------------------------- satellite: layout failure modes


def test_overlapping_state_regions_raise():
    """A corrupt state plan whose leaf slots alias must fail at
    materialization, before any bytes are shared."""
    _, _, _, _, sp = _setup("qwen3-0.6b")
    squashed = dataclasses.replace(
        sp,
        leaves=[dataclasses.replace(l, offset=0) for l in sp.leaves],
    )
    if len(squashed.leaves) < 2:
        pytest.skip("needs >= 2 leaves to overlap")
    with pytest.raises(ValueError, match="overlap"):
        ArenaLayout.from_state_plan(squashed)


def test_leaf_offset_past_total_size_raises():
    _, _, _, _, sp = _setup("qwen3-0.6b")
    pushed = dataclasses.replace(
        sp,
        leaves=[
            dataclasses.replace(sp.leaves[0], offset=sp.total_size),
            *sp.leaves[1:],
        ],
    )
    with pytest.raises(ValueError, match="outside"):
        ArenaLayout.from_state_plan(pushed)


def test_v1_bundle_state_materialization_raises_clearly():
    """A v1 bundle ships no state plan; asking for its state arena must
    say so, not die on an attribute lookup."""
    with pytest.raises(ValueError, match="v1 bundle"):
        ArenaLayout.from_state_plan(None)
    # the graceful path: a v1-shimmed UnifiedPlan materializes only the
    # activation half
    from repro.core.planner import plan_records
    from repro.core.records import make_records
    from repro.core.unified import UnifiedPlan

    up = UnifiedPlan(
        activation=plan_records(
            make_records([(0, 1, 128)]), use_cache=False
        ),
        state=None,
        fingerprint="v1-shim",
    )
    act, state = ArenaLayout.from_unified(up)
    assert act is not None and state is None


# ------------------------------------------------- backend differential


def _slot_bytes(buf, sp, slot) -> bytes:
    """The bytes of one slot's region of a state buffer."""
    raw = np.asarray(buf).view(np.uint8)
    return raw[slot * sp.slot_stride : (slot + 1) * sp.slot_stride].tobytes()


def _active_at(case: str, step: int, n_slots: int) -> np.ndarray:
    """Which slots decode at ``step``: all of them; a mixed mask that
    changes from step to step; or one slot, as a prompt feed runs."""
    if case == "mixed":
        return np.array([(b + step) % 3 != 0 for b in range(n_slots)])
    if case == "prompt":
        return np.arange(n_slots) == 1
    return np.ones(n_slots, bool)


DECODE_CASES = [pytest.param(a, "all", id=a) for a in ARCHS] + [
    pytest.param(a, case, id=f"{a}-{case}")
    for case in ("mixed", "prompt", "reset", "block")
    for a in ARCHS
]


def _assert_blocks_identical(arch, cfg, params, sp, res, resident, baseline):
    """Scan blocks of waves on the resident and reference states, from
    one slot idle and the others stopping at different waves: the same
    tokens, and after each block the baseline's cache laid out by the
    plan, byte for byte."""
    n = sp.n_slots
    sampler = TokenSampler(SamplingParams(), max_len=sp.max_len)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, size=(n, 1)), jnp.int32)
    pos = jnp.zeros(n, jnp.int32)
    active = jnp.asarray(np.arange(n) != 1)
    done = jnp.zeros(n, bool)
    budget = jnp.asarray(2 + 3 * np.arange(n), jnp.int32)
    keys = sampler.init_keys(0, n)
    for blk in range(3):
        args = (params, tokens, pos, active, done, budget, keys,
                jnp.int32(-1))
        r = resident.decode_block(*args, length=3, sampler=sampler)
        b = baseline.decode_block(*args, length=3, sampler=sampler)
        np.testing.assert_array_equal(np.asarray(r.wave_tokens),
                                      np.asarray(b.wave_tokens))
        np.testing.assert_array_equal(np.asarray(r.emitted),
                                      np.asarray(b.emitted))
        assert (np.asarray(resident.buf).tobytes()
                == np.asarray(res.init_buffer(baseline.caches)).tobytes()), (
            f"{arch}: state diverged in block {blk}")
        tokens, pos, done, budget, keys = (r.tokens, r.pos, r.done,
                                           r.budget, r.keys)


@pytest.mark.parametrize("arch,case", DECODE_CASES)
def test_decode_byte_identical_to_xla_allocated_baseline(arch, case):
    """Acceptance: with residency on, decode logits AND the cache state
    after every step are byte-identical to the XLA-allocated pytree
    baseline — across attention, SSM, and hybrid shared-attn caches.
    The resident step updates the buffer in place, so beyond that: the
    regions of slots that did not decode keep every byte (``mixed``,
    and ``prompt``, one slot active as a prompt feed runs), and a reset
    between steps zeroes exactly the reset slots' regions (``reset``).
    The scan block runs the same in-place wave (``block``)."""
    n = 2 if case == "all" else 3
    cfg, model, params, caches, sp = _setup(arch, n_slots=n)
    res = StateResidency(sp, caches, n_slots=n)
    resident = ResidentState(model, res, caches)
    baseline = ResidentState(
        model, PytreeAddressing(model, n_slots=n, max_len=sp.max_len), caches
    )
    assert resident.live_bytes == sp.total_size
    if case == "block":
        _assert_blocks_identical(arch, cfg, params, sp, res, resident,
                                 baseline)
        return

    rng = np.random.default_rng(0)
    pos = np.zeros(n, np.int32)
    for step in range(5):
        if case == "reset" and step == 3:
            keep = np.arange(n) != 1
            before = np.asarray(resident.buf)
            resident.reset(keep)
            baseline.reset(keep)
            pos[~keep] = 0
            for b in range(n):
                got = _slot_bytes(resident.buf, sp, b)
                want = (_slot_bytes(before, sp, b) if keep[b]
                        else bytes(len(got)))
                assert got == want, f"{arch}: reset touched slot {b}"
        act = _active_at(case, step, n)
        tok = jnp.asarray(
            rng.integers(0, cfg.vocab, size=(n, 1)), jnp.int32
        )
        before = np.asarray(resident.buf)
        l_res = resident.decode(params, tok, jnp.asarray(pos),
                                jnp.asarray(act))
        l_base = baseline.decode(params, tok, jnp.asarray(pos),
                                 jnp.asarray(act))
        pos += act
        np.testing.assert_array_equal(
            np.asarray(l_res), np.asarray(l_base),
            err_msg=f"{arch}: logits diverged at step {step}",
        )
        for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(resident.caches)[0],
            jax.tree_util.tree_flatten_with_path(baseline.caches)[0],
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
                f"{arch}: cache leaf {jax.tree_util.keystr(p)} diverged "
                f"at step {step}"
            )
        # every byte of the buffer, padding included, is the baseline's
        # cache laid out by the plan
        assert (np.asarray(resident.buf).tobytes()
                == np.asarray(res.init_buffer(baseline.caches)).tobytes())
        for b in np.flatnonzero(~act):
            assert _slot_bytes(resident.buf, sp, b) == \
                _slot_bytes(before, sp, b), (
                    f"{arch}: inactive slot {b} changed at step {step}")
    # slot reset round-trips the arena identically too
    keep = np.array([True] + [False] * (n - 1))
    resident.reset(keep)
    baseline.reset(keep)
    for (_, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(resident.caches)[0],
        jax.tree_util.tree_flatten_with_path(baseline.caches)[0],
    ):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_serves_identical_tokens_with_residency_on_and_off(arch):
    """End-to-end differential: staggered requests, slot reuse, resets —
    the full serving loop emits the same tokens either way."""
    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    outs = []
    for residency in (True, False):
        engine = InferenceEngine(
            cfg, params, n_slots=2, max_len=48, state_residency=residency,
        )
        assert engine.memory_report.state_residency is residency
        rng = np.random.default_rng(7)
        for _ in range(5):
            engine.submit(
                rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                max_new_tokens=3,
            )
        done = engine.run_until_done()
        outs.append({r.request_id: r.tokens for r in done})
    assert outs[0] == outs[1]


# ----------------------------------------------------- engine integration


def test_engine_live_state_bytes_equal_planned():
    """Acceptance: ONE state allocation of exactly StatePlan.total_size."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32)
    rep = engine.memory_report
    assert rep.state_residency
    assert rep.state_live_bytes == rep.state_planned_bytes
    assert rep.state_live_bytes == rep.state_plan.total_size
    assert engine.state.live_bytes == rep.state_plan.total_size
    assert engine.state.buf.dtype == jnp.dtype(cfg.dtype)
    assert "state residency: ON" in rep.summary()
    # the per-slot figure is the exact plan region size, not a truncating
    # integer division of measured bytes
    assert rep.cache_bytes_per_slot == rep.state_plan.bytes_per_slot
    assert rep.cache_bytes_per_slot * engine.n_slots == (
        rep.state_plan.total_size
    )
    # serving does not grow the allocation: same buffer size after work
    engine.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    engine.run_until_done()
    assert engine.state.live_bytes == rep.state_plan.total_size


def test_decode_consumes_the_donated_buffer():
    """The single-allocation claim is donation, not just sizing: after a
    wave, the PREVIOUS buffer value must be consumed (donated to XLA and
    reused in place), never left alive as a second state copy."""
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32)
    planned = engine.memory_report.state_plan.total_size
    engine.submit(np.arange(4, dtype=np.int32), max_new_tokens=5)
    for _ in range(3):
        before = engine.state.buf
        engine.step()  # active request -> at least one decode wave ran
        assert before.is_deleted(), (
            "decode did not consume the donated state buffer — two live "
            "state copies instead of one"
        )
        assert engine.state.buf.nbytes == planned


def test_zero_init_buffer_equals_packed_init_cache():
    """The engine zero-inits the flat buffer without materializing a
    cache pytree; that must be byte-identical to packing the models'
    actual init_cache output (the all-zero contract)."""
    _, model, _, caches, sp = _setup("zamba2-7b")
    res = StateResidency(sp, caches, n_slots=2)
    zeroed = np.asarray(res.init_buffer())
    packed = np.asarray(res.init_buffer(caches))
    np.testing.assert_array_equal(zeroed, packed)


def test_env_escape_hatch_disables_residency(monkeypatch):
    assert residency_enabled(None)
    for off in ("off", "0", "false", "NO"):
        monkeypatch.setenv("REPRO_STATE_RESIDENCY", off)
        assert not residency_enabled(None)
        assert residency_enabled(True)  # explicit kwarg wins
    monkeypatch.setenv("REPRO_STATE_RESIDENCY", "off")
    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, n_slots=2, max_len=32)
    rep = engine.memory_report
    assert not rep.state_residency
    assert isinstance(engine.state.addressing, PytreeAddressing)
    assert rep.state_live_bytes == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(engine.caches)
    )
    assert "state residency: off" in rep.summary()
    # serving still works on the legacy path
    engine.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    assert len(engine.run_until_done()) == 1


def test_bundle_served_engine_is_resident_with_zero_layout_work(tmp_path):
    """The residency buffer must come straight from the bundled StatePlan:
    zero traces, zero planner calls, zero state layouts — and live bytes
    equal to the artifact's own state total."""
    from repro.analysis import counters
    from repro.core.unified import PlanSession
    from repro.launch.compile import compile_and_publish

    cfg = get_reduced("qwen3-0.6b")
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    compile_and_publish(cfg, tmp_path, n_slots=2, max_len=32)
    with counters.capture(
        "trace_calls", "plan_calls", "state_plan_calls"
    ) as cap:
        engine = InferenceEngine(
            cfg, params, n_slots=2, max_len=32,
            session=PlanSession.from_manifest(tmp_path),
        )
    assert all(d == 0 for d in cap.deltas().values()), cap.deltas()
    rep = engine.memory_report
    assert rep.plan_source == "bundle"
    assert rep.state_residency
    assert rep.state_live_bytes == engine.plan_bundle.state_plan.total_size
    engine.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    assert len(engine.run_until_done()) == 1
