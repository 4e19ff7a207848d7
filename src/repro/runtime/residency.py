"""Plan-backed state residency: the engine's cross-step state in ONE
device buffer, laid out by the :class:`~repro.core.unified.StatePlan`,
and the one serving backend that holds it.

PR 4 made the cross-step slot/KV layout a first-class planned object —
but it was accounting only: the engine's cache pytree was still a bag of
XLA-allocated buffers whose placement the plan merely described. This
module closes that gap (the MAFAT/FlashMem observation that the §4 win
comes from *owning* the physical buffers, not modeling them).

The state is reached through an *addressing*: an object that says how a
program enters the state, hands it to the model, leaves it and resets
it. There is one per kind, and everything else is written once:

* :class:`StateResidency` (entry prefix ``resident``, the symmetric
  state) binds a cache pytree *structure* to a StatePlan: every (slot,
  leaf) cell is addressed by the plan's
  :meth:`~repro.core.unified.StatePlan.leaf_view_spec` in one flat
  buffer of the cache's element dtype (a
  :class:`~repro.runtime.arena.DeviceArena`; no lane-padded byte views
  on the TPU), and every leaf sits at one offset in every slot region.
  The model takes the buffer as a :class:`SlotBuffer`
  (``models/state_view.py``): each layer reads its cache as a view of it
  and writes back only the K/V rows and SSM states it changed, and a
  reset zeroes only the reset slots' regions;
* :class:`~repro.runtime.paging.PagedStateResidency` (``paged``)
  reaches the same logical layout through per-slot page tables over a
  pool of physical pages, and owns the pool;
* :class:`PytreeAddressing` (``pytree``) is the XLA-allocated cache
  pytree, reallocated by value every program
  (``REPRO_STATE_RESIDENCY=off``). It is the reference of the residency
  differential tests: decode outputs through the arena views are
  byte-identical to it.

:class:`ResidentState` serves any of them through one set of programs
(:func:`decode_impl`, :func:`reset_impl`, :func:`block_impl`). Both
buffers are a DONATED argument of every program, which returns its
successor, so XLA reuses the same physical allocation every wave — live
device state bytes equal ``StatePlan.total_size`` exactly, one
allocation for the engine's whole cross-step lifecycle.

The initial buffer is packed on the host through the *numpy* arena
(``Arena.store`` over the same leaf-view spec) and shipped with one
``device_put`` — bounds-checked byte placement, no extra jit compile on
the cold-start path.

**Zero-compile serving (PlanBundle v3).** The program factories are
module-level so three consumers provably lower the exact same
computation: the serving backend here, the AOT compiler
(``runtime/aot.py``, which serializes the compiled executables into the
bundle), and the static decode lint. The backend dispatches
load-or-compile per program: a deserialized AOT executable when the
bundle ships one, else a :class:`_LazyJit` — a ``jax.jit`` wrapper that
charges the module-global ``COMPILE_CALLS`` counter whenever a call
actually compiles, so the v3 zero-compile guarantee is counter-asserted
with the same discipline as the zero-trace/zero-plan ones.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import counters
from repro.core.artifact import block_entry_name
from repro.core.unified import PagedStatePlan, StatePlan
from repro.models.state_view import LeafSlot, SlotBuffer, scoped_call
from repro.runtime.arena import Arena, ArenaLayout, DeviceArena

# Decode-path XLA compiles (lazy jit cache misses + explicit AOT
# compiles via count_compile). NOT a count of every backend compilation
# the process ever does — eager-op warmup and host-side utility jits are
# out of scope; this counts the serving-path decode functions the v3
# bundle exists to pre-compile. Asserted ``== 0`` when serving from a v3
# bundle (tests + CI), mirroring TRACE_CALLS / PLAN_CALLS.
COMPILE_CALLS = 0


# Executions of the single-wave decode program, one per backend
# ``decode()`` call: host-loop waves and every prompt-feed token (the
# registry's ``decode_dispatches``).
DECODE_DISPATCHES = 0


def count_compile(n: int = 1) -> None:
    """Charge ``n`` decode-path XLA compiles (AOT builds call this
    explicitly; lazy jits are counted by :class:`_LazyJit`)."""
    global COMPILE_CALLS
    COMPILE_CALLS += n


def decode_and_wait(decode: Callable, *args) -> tuple[Any, Any]:
    """Run one decode-program execution and wait for its new state:
    ``decode(*args) -> (logits, state)``. The backend's ``decode()``,
    under the host spans ``repro.state.decode`` (enqueue plus wait) and
    ``repro.state.wait``, and counted in ``DECODE_DISPATCHES``."""
    global DECODE_DISPATCHES
    with counters.span("repro.state.decode"):
        logits, state = decode(*args)
        DECODE_DISPATCHES += 1
        # synchronize before the engine mutates its host-side buffers —
        # see the _step_tokens race note in runtime/engine.py
        with counters.span("repro.state.wait"):
            jax.block_until_ready(state)
    return logits, state


class _LazyJit:
    """``jax.jit`` that counts actual compiles.

    A call that misses the jit cache compiles; one that hits does not.
    The cache-size delta is the exact signal (``_cache_size`` is
    jax-private; the installed jax has it, and the zero-compile tests
    fail loudly if a release drops it)."""

    def __init__(self, fn: Callable, **jit_kwargs: Any):
        self._jitted = jax.jit(fn, **jit_kwargs)

    def __call__(self, *args: Any) -> Any:
        before = self._jitted._cache_size()
        out = self._jitted(*args)
        count_compile(self._jitted._cache_size() - before)
        return out


# Donated argument positions of the state in every addressing's
# programs (the paged page table rides LAST and is never donated),
# shared by the serving jits here and the AOT lowering in
# runtime/aot.py — donation must survive serialization (audited by
# analysis/decode_lint.lint_executables). The reference pytree donates
# nothing (``Addressing.donated``).
DECODE_DONATE = (2,)  # (params, tokens, BUF, pos, active, *args)
RESET_DONATE = (0,)  # (BUF, keep, *args)
BLOCK_DONATE = (1,)  # (params, BUF, tokens, pos, active, ..., *args)


def residency_enabled(override: bool | None = None) -> bool:
    """The ``REPRO_STATE_RESIDENCY`` knob: on unless explicitly disabled
    (``off``/``0``/``false``/``no``). An explicit ``override`` (engine
    kwarg) wins over the environment."""
    if override is not None:
        return override
    val = os.environ.get("REPRO_STATE_RESIDENCY", "on").strip().lower()
    return val not in ("off", "0", "false", "no")


def state_buffer_aval(state_plan: StatePlan) -> jax.ShapeDtypeStruct:
    """The device buffer that holds ``state_plan``'s state, typed by the
    cache's element dtype: flat for a symmetric plan, one row per
    physical page for a paged plan (so the page-table gather and scatter
    never reshape the whole buffer). The AOT lowering and the decode lint
    take the buffer's shape from here; the flat length is
    :meth:`DeviceArena.length`, which the resident backend allocates."""
    dtype = jnp.dtype(state_plan.leaves[0].dtype)
    if isinstance(state_plan, PagedStatePlan):
        return jax.ShapeDtypeStruct(
            (state_plan.n_pages_total, state_plan.page_size // dtype.itemsize),
            dtype,
        )
    return jax.ShapeDtypeStruct(
        (DeviceArena.length(state_plan.total_size, dtype),), dtype
    )


def _slot_axis(keypath) -> int:
    """Which leaf axis carries the slot (request batch) dimension.

    The decoder cache contract (``models/transformer.init_cache``): leaves
    under ``"period"`` are stacked over ``n_periods`` first, so slots are
    axis 1; everything else (``"remainder"``, shared blocks) carries slots
    on axis 0. Validated against ``n_slots`` at binding time, so a model
    breaking the contract fails loudly, not silently."""
    if keypath and getattr(keypath[0], "key", None) == "period":
        return 1
    return 0


class Addressing:
    """How a state program reaches the engine's cross-step state. The
    defaults are the cache pytree's, passed by value.

    A program enters the state once (:meth:`enter`, from its state
    argument and the :meth:`args` after its usual ones), hands the model
    :meth:`view` of what it carries and takes back :meth:`unview` of
    what the model returns (a scan block does both every wave), and
    leaves once (:meth:`leave`: the state argument's successor). A reset
    is :meth:`reset` between a view and an unview. The serving hooks
    :meth:`admit` and :meth:`retire` do nothing here."""

    prefix = "pytree"  # AOT entry names: ``{prefix}_decode`` & co.
    donate = False  # programs donate their state argument
    pool = None  # the page pool (paged addressing only)

    def donated(self, argnums: tuple) -> tuple:
        """``argnums`` where programs donate the state, else none."""
        return argnums if self.donate else ()

    def args(self) -> tuple:
        """A program's arguments after its usual ones, at serving time."""
        return ()

    def arg_avals(self) -> tuple:
        """:meth:`args` shape-level, for lowering."""
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in self.args())

    def enter(self, value, *args):
        return value

    def leave(self, carry, value, *args):
        return carry

    def view(self, carry):
        return carry

    def unview(self, state):
        return state

    def reset(self, model, state, keep):
        """``state`` with the slots where ``keep`` is False set to the
        models' all-zero initial state."""
        return model.reset_slots(state, keep)

    def unpack(self, value, *args) -> Any:
        """The cache pytree held in ``value`` (for inspection)."""
        return value

    def live_bytes(self, value) -> int:
        return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(value))

    def admit(self, slot: int, needed_len: int, *, rid: int,
              wave: int) -> None:
        """Called before ``slot`` takes request ``rid``, whose cache
        never grows past ``needed_len`` rows."""

    def retire(self, slot: int, wave: int) -> None:
        """Called when ``slot``'s request finishes at ``wave``."""


class PytreeAddressing(Addressing):
    """The reference addressing: the cache pytree XLA allocates, passed
    to every program by value and returned anew (not donated)."""

    def __init__(self, model, *, n_slots: int, max_len: int):
        self._init = lambda: model.init_cache(n_slots, max_len)
        self.aval = jax.eval_shape(self._init)

    def init_buffer(self, caches: Any = None):
        return self._init() if caches is None else caches


class StateResidency(Addressing):
    """Bind a cache-pytree structure to a StatePlan's leaf-view spec.

    ``template`` may be concrete arrays or ``jax.eval_shape`` structs —
    only structure, shapes and dtypes are read. Construction validates
    the binding completely (path sets match, dtypes match, per-slot byte
    sizes match the plan, the slot axis really has ``n_slots`` extent),
    so a stale or foreign state plan fails here with a clear error
    instead of corrupting decode state.

    As an :class:`Addressing` it is the symmetric state: the model takes
    the buffer as a :class:`SlotBuffer`, and a reset zeroes the reset
    slots' regions."""

    prefix = "resident"
    donate = True

    def __init__(
        self,
        state_plan: StatePlan,
        template: Any,
        *,
        n_slots: int,
        layout: "ArenaLayout | None" = None,
    ):
        if state_plan.n_slots != n_slots:
            raise ValueError(
                f"state plan lays out {state_plan.n_slots} slots, engine "
                f"serves {n_slots}"
            )
        self.state_plan = state_plan
        self.n_slots = n_slots
        # callers that already materialized (and validated) the layout
        # from this plan pass it in; from_state_plan re-validates
        if layout is None:
            layout = ArenaLayout.from_state_plan(state_plan)

        leaves, self.treedef = jax.tree_util.tree_flatten_with_path(template)
        views_by_path: dict[str, list] = {}
        for view in state_plan.leaf_view_spec():
            views_by_path.setdefault(view.path, []).append(view)

        tmpl_paths = {jax.tree_util.keystr(p) for p, _ in leaves}
        if tmpl_paths != set(views_by_path):
            missing = sorted(tmpl_paths - set(views_by_path))
            extra = sorted(set(views_by_path) - tmpl_paths)
            raise ValueError(
                f"state plan does not cover this cache pytree: "
                f"{len(missing)} leaf(s) unplanned {missing[:3]}, "
                f"{len(extra)} planned leaf(s) absent {extra[:3]}"
            )

        # per-leaf binding: (path, slot_axis, per-slot shape, dtype, views)
        self._bindings = []
        for keypath, leaf in leaves:
            path = jax.tree_util.keystr(keypath)
            axis = _slot_axis(keypath)
            shape = tuple(int(d) for d in leaf.shape)
            if axis >= len(shape) or shape[axis] != n_slots:
                raise ValueError(
                    f"state leaf {path!r}: expected {n_slots} slots on "
                    f"axis {axis} of shape {shape}"
                )
            dt = jnp.dtype(leaf.dtype)
            per_slot_shape = shape[:axis] + shape[axis + 1 :]
            per_slot_nbytes = int(np.prod(per_slot_shape)) * dt.itemsize
            views = sorted(views_by_path[path], key=lambda v: v.slot)
            for v in views:
                if v.dtype != dt.name:
                    raise ValueError(
                        f"state leaf {path!r}: plan dtype {v.dtype} != "
                        f"cache dtype {dt.name}"
                    )
                if v.used_nbytes != per_slot_nbytes:
                    raise ValueError(
                        f"state leaf {path!r}: plan expects "
                        f"{v.used_nbytes} B/slot, cache carries "
                        f"{per_slot_nbytes} B/slot"
                    )
            self._bindings.append((path, axis, per_slot_shape, dt, views))
        dtypes = sorted({dt.name for _p, _a, _s, dt, _v in self._bindings})
        if len(dtypes) != 1:
            raise ValueError(
                f"state residency holds one element dtype per buffer; "
                f"this cache pytree mixes {dtypes}"
            )
        self.dtype = self._bindings[0][3]
        self.arena = DeviceArena(layout, self.dtype)
        # where each leaf lives in a slot region, for the in-place decode
        item = self.dtype.itemsize
        self.slot_elems = state_plan.slot_stride // item
        slots = []
        for path, axis, per_slot_shape, _dt, views in self._bindings:
            col = views[0].offset // item
            if any(v.offset != v.slot * state_plan.slot_stride + col * item
                   for v in views):
                raise ValueError(
                    f"state leaf {path!r}: not at one offset in every slot"
                )
            # a leaf stacked over the periods holds one layer per period
            slots.append(LeafSlot(col, per_slot_shape[axis:]))
        self.slots = jax.tree_util.tree_unflatten(self.treedef, slots)
        self.aval = state_buffer_aval(state_plan)

    @property
    def total_size(self) -> int:
        return self.state_plan.total_size

    def init_buffer(self, caches: Any = None):
        """A fresh state buffer: zeroed (``caches=None`` — the models'
        ``init_cache`` contract is all-zero state, so the engine never
        materializes a cache pytree on the residency path), or packed
        from concrete initial caches.

        Concrete packing goes host-side through the bounds-checked numpy
        :class:`Arena` (same leaf-view spec as the device views), then
        one ``device_put`` — correct for any initial cache contents, and
        no extra jit compile on the cold-start path."""
        if caches is None:
            return self.arena.allocate()
        host = Arena(self.arena.layout)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(caches)
        if treedef != self.treedef:
            raise ValueError(
                "initial caches do not match the bound pytree structure"
            )
        for (_, leaf), (path, axis, _pss, dt, views) in zip(
            leaves, self._bindings
        ):
            arr = np.asarray(leaf)
            for view in views:
                host.store(
                    view.tensor_id, np.take(arr, view.slot, axis=axis)
                )
        # jnp.array COPIES into a device-owned buffer: device_put of the
        # host arena can zero-copy alias numpy memory on CPU, which is
        # unsafe to donate through the decode jits
        return jnp.array(host.buf[: self.arena.nbytes].view(self.dtype))

    def view(self, buf) -> SlotBuffer:
        """``buf`` as the decode step reads and writes it in place: each
        layer's cache a view of the buffer at the plan's offsets."""
        return SlotBuffer(buf, self.n_slots, self.slot_elems, self.slots)

    def unview(self, state: SlotBuffer):
        return state.buf

    def reset(self, model, state: SlotBuffer, keep) -> SlotBuffer:
        del model
        state.zero_slots(keep)
        return state

    def unpack(self, buf) -> Any:
        """The cache pytree as copies out of ``buf`` — every leaf rebuilt
        from its per-slot cells at the plan's offsets (for inspection;
        the decode programs read the buffer in place). Its operations
        carry the name scope ``state.unpack``."""
        return scoped_call("state.unpack", self._unpack, buf)

    def _unpack(self, buf) -> Any:
        out = []
        for _path, axis, per_slot_shape, dt, views in self._bindings:
            per_slot = [
                self.arena.view(buf, v.tensor_id, per_slot_shape, dt)
                for v in views
            ]
            out.append(jnp.stack(per_slot, axis=axis))
        return jax.tree_util.tree_unflatten(self.treedef, out)


def state_addressing(
    state_plan: StatePlan,
    template: Any,
    *,
    n_slots: int,
    layout: "ArenaLayout | None" = None,
) -> StateResidency:
    """The addressing a state plan is served through: paged for a
    :class:`~repro.core.unified.PagedStatePlan`, else symmetric."""
    from repro.runtime.paging import PagedStateResidency  # builds on this

    cls = (PagedStateResidency if isinstance(state_plan, PagedStatePlan)
           else StateResidency)
    return cls(state_plan, template, n_slots=n_slots, layout=layout)


@dataclasses.dataclass
class BlockOut:
    """Device handles from one scan-block dispatch — NOTHING here has
    been fetched. ``tokens``/``pos``/``done``/``budget``/``keys`` are the
    post-block carry (the engine chains the next block's dispatch off
    them without a host sync); ``wave_tokens``/``emitted`` are the
    per-wave outputs the engine fetches once per block when absorbing."""

    tokens: Any  # (n_slots, 1) int32 — last token per slot
    pos: Any  # (n_slots,) int32
    done: Any  # (n_slots,) bool — stopped mid-block (EOS/budget/max_len)
    budget: Any  # (n_slots,) int32 — remaining new-token budget
    keys: Any  # (n_slots, 2) uint32 — per-slot PRNG keys
    wave_tokens: Any  # (K, n_slots) int32 — token chosen at each wave
    emitted: Any  # (K, n_slots) bool — slot actually emitted at that wave


def _block_wave(model, sampler, params, caches, tokens, pos, active, done,
                budget, keys, eos):
    """One scan wave, shared by every addressing (only the state it
    carries differs: a cache pytree, or a SlotBuffer updated in place):
    decode at ``active & ~done``, then the sampler's on-device token
    selection + stop bookkeeping. Inactive/frozen slots keep their token and
    position, so the cache scatter stays idempotent for them — the same
    invariant the host loop relies on."""
    step_active = active & jnp.logical_not(done)
    logits, new_caches = model.decode_step(
        params, tokens, caches, pos, active=step_active
    )
    keys, tokens, pos, done, budget = sampler.advance(
        logits, keys, tokens, pos, step_active, done, budget, eos
    )
    carry = (tokens, pos, done, budget, keys)
    return new_caches, carry, (tokens[:, 0], step_active)


# ------------------------------------------------- jitted decode functions
#
# Module-level factories for everything the backend jits. The serving
# backend, the AOT bundle compiler (runtime/aot.py) and the static
# decode lint all lower THESE functions — so "the bundled executable is
# the executable the engine would have compiled" holds by construction,
# and the differential tests only need to check numerics, not identity.
# The addressing's extra arguments (the paged state's page table) come
# last in every program.


def decode_impl(model, addressing: Addressing) -> Callable:
    """One decode wave: ``(params, tokens, state, pos, active, *args) ->
    (logits, state')``. On the symmetric state each layer writes back
    only the rows and states it changed."""

    def decode_step(params, tokens, buf, pos, active, *args):
        logits, state = model.decode_step(
            params, tokens, addressing.view(addressing.enter(buf, *args)),
            pos, active=active,
        )
        return logits, addressing.leave(addressing.unview(state), buf, *args)

    return decode_step


def reset_impl(model, addressing: Addressing) -> Callable:
    """Slot reset, ``(state, keep, *args) -> state'``: the slots with
    ``keep`` False get the models' all-zero initial state. The symmetric
    state zeroes just their regions and touches no other byte; the paged
    state zeroes every page they still map."""

    def reset_slots(buf, keep, *args):
        state = addressing.reset(
            model, addressing.view(addressing.enter(buf, *args)), keep
        )
        return addressing.leave(addressing.unview(state), buf, *args)

    return reset_slots


def block_impl(
    model, addressing: Addressing, sampler, length: int
) -> Callable:
    """``length`` decode waves in one ``lax.scan``, sampling + stop
    detection on device (see ``_block_wave``). The state is entered
    before the scan and left after it: the symmetric buffer itself is
    the scan carry, while the paged state is gathered through the tables
    once and scattered back once."""

    def decode_block(params, buf, tokens, pos, active, done, budget, keys,
                     eos, *args):
        def body(carry, _):
            value, tokens, pos, done, budget, keys = carry
            state, (tokens, pos, done, budget, keys), out = _block_wave(
                model, sampler, params, addressing.view(value), tokens,
                pos, active, done, budget, keys, eos,
            )
            return (addressing.unview(state), tokens, pos, done, budget,
                    keys), out

        carry, (toks, emitted) = jax.lax.scan(
            body,
            (addressing.enter(buf, *args), tokens, pos, done, budget, keys),
            None, length=length,
        )
        value, tokens, pos, done, budget, keys = carry
        return ((addressing.leave(value, buf, *args), tokens, pos, done,
                 budget, keys), toks, emitted)

    return decode_block


class ResidentState:
    """The serving backend: the engine's cross-step state and the
    programs that step it, whichever addressing reaches it.

    On both buffers ``decode``/``reset``/``decode_block`` donate the
    state to their programs and keep its successor, so XLA writes the
    new state into the same physical allocation every wave — the
    planned layout IS the live layout, and on the symmetric state
    ``live_bytes == StatePlan.total_size`` for the engine's whole
    lifetime."""

    def __init__(
        self,
        model,
        addressing: Addressing,
        init_caches: Any = None,
        *,
        executables: "dict[str, Any] | None" = None,
    ):
        self.model = model
        self.addressing = addressing
        self.buf = addressing.init_buffer(init_caches)
        # load-or-compile: a deserialized AOT executable from the bundle
        # when present (zero XLA compiles), else a counted lazy jit of
        # the SAME program factory the AOT compiler lowered
        self._execs = executables or {}
        prefix = addressing.prefix
        self._decode = self._program(
            f"{prefix}_decode", decode_impl(model, addressing), DECODE_DONATE
        )
        self._reset = self._program(
            f"{prefix}_reset", reset_impl(model, addressing), RESET_DONATE
        )
        self._block_jits: dict[int, Any] = {}  # scan length -> callable

    def _program(self, entry: str, fn: Callable, donate: tuple):
        return self._execs.get(entry) or _LazyJit(
            fn, donate_argnums=self.addressing.donated(donate)
        )

    @property
    def residency(self) -> bool:
        """The state lives in a planned buffer (donated to every
        program), not in the reference's XLA-allocated pytree."""
        return self.addressing.donate

    def decode(self, params, tokens, pos, active):
        logits, self.buf = decode_and_wait(
            self._decode, params, tokens, self.buf, pos, active,
            *self.addressing.args(),
        )
        return logits

    def reset(self, keep):
        self.buf = self._reset(
            self.buf, jnp.array(keep), *self.addressing.args()
        )
        jax.block_until_ready(self.buf)

    def decode_block(self, params, tokens, pos, active, done, budget, keys,
                     eos, *, length, sampler) -> BlockOut:
        """``length`` decode waves in ONE dispatch: ``lax.scan`` over the
        state with on-device sampling and stop detection. Returns device
        handles only — no host sync here; the engine fetches the
        per-wave outputs when it absorbs the block, and may chain the
        next block's dispatch off the returned carry first. Page tables
        change only at admission, and the engine chains blocks only when
        nothing is queued, so an in-flight block always holds the
        current table.

        An AOT executable covers the configured full-size block only
        (tail blocks have engine-chosen shorter lengths and lazy-compile
        — the bundle's serve fingerprint pins block size and sampling, so
        a pack entry that matches is safe to run)."""
        jitted = self._block_jits.get(length)
        if jitted is None:
            jitted = self._program(
                block_entry_name(self.addressing.prefix, length),
                block_impl(self.model, self.addressing, sampler, length),
                BLOCK_DONATE,
            )
            self._block_jits[length] = jitted
        carry, toks, emitted = jitted(
            params, self.buf, tokens, pos, active, done, budget, keys, eos,
            *self.addressing.args(),
        )
        self.buf, tokens, pos, done, budget, keys = carry
        return BlockOut(tokens=tokens, pos=pos, done=done, budget=budget,
                        keys=keys, wave_tokens=toks, emitted=emitted)

    def admit(self, slot: int, needed_len: int, *, rid: int,
              wave: int) -> None:
        """Before ``slot`` takes request ``rid``: the paged state maps
        the pages it needs (raising
        :class:`~repro.runtime.paging.PagedOutOfPagesError`, with
        nothing changed, when the pool cannot cover them)."""
        self.addressing.admit(slot, needed_len, rid=rid, wave=wave)

    def retire(self, slot: int, wave: int) -> None:
        """When ``slot``'s request finishes: the paged state frees its
        pages."""
        self.addressing.retire(slot, wave)

    @property
    def pages(self):
        """The page pool (:class:`~repro.runtime.paging.PagePool`), or
        None where the state is not paged."""
        return self.addressing.pool

    @property
    def caches(self) -> Any:
        """The cache pytree, copied out of the state buffer (for
        inspection/tracing; decode never materializes this on the
        host)."""
        return self.addressing.unpack(self.buf, *self.addressing.args())

    @property
    def live_bytes(self) -> int:
        """Device bytes holding live state: the whole buffer, the cache
        pytree's leaves, or the paged state's live pool pages."""
        return self.addressing.live_bytes(self.buf)
