"""Paged state backend: the residency buffer addressed through per-slot
page tables (ROADMAP open item 2 — §4 shared objects at page granularity).

The symmetric :class:`~repro.runtime.residency.ResidentState` gives every
slot a full ``max_len`` region, so a 64-token request in a 4096-token
bucket strands ~98% of its planned state bytes. This module keeps the
*logical* layout identical — the same
:class:`~repro.core.unified.StatePlan` leaves, offsets and strides the
whole codebase reasons about — but backs it with a pool of fixed-size
physical pages (:class:`~repro.core.unified.PagedStatePlan`):

* :class:`PagedStateResidency` re-binds the cache pytree to the plan
  through a page-table indirection: ``unpack`` gathers each slot's
  logical region from its table row (``jnp.take`` over the buffer's
  page rows), ``pack`` scatters it back — one gather + one scatter per
  decode wave, all shapes static, so the decode jit stays a fixed
  program and the table is plain int32 *data* (no retrace, no
  recompile when the mapping changes);
* physical page 0 is the reserved all-zero **null page**: unmapped
  logical pages read as zeros through it, and every scatter row aimed
  at it provably carries zeros (unmapped bytes are zeros on the way in
  and the decode masks its cache updates by ``active``), so duplicate
  scatter indices are benign;
* :class:`PagedResidentState` adds the serving-time bookkeeping:
  allocate-on-admit (:meth:`~PagedResidentState.allocate_slot` maps the
  pages a request's ``needed_len`` intersects, refusing with
  :class:`PagedOutOfPagesError` when the pool cannot cover it) and
  free-on-retire (:meth:`~PagedResidentState.free_slot`), with a page
  log mirroring the engine's slot log for the §4-style audit
  (``shared_objects.from_page_log``).

**Byte-identity discipline.** Retirement frees a slot's pages but does
NOT clear its table row (*lazy invalidation*): the symmetric baseline
never zeroes a retired slot (reset happens at the next admit), so the
retired slot's stale bytes must stay readable for the cache-leaf
differential to hold. Re-admission prefers (1) the slot's own stale
pages, then (2) never-mapped free pages, and only then (3) steals
another retired slot's stale page — and at the default pool size
(``n_slots * pages_per_slot``) case (3) provably never happens, so
paged decode is unconditionally byte-identical to the symmetric
baseline there. Reset-at-admit zeroes every page the slot still maps
(stale ones included), exactly matching the baseline's full-region
wipe.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.artifact import block_entry_name
from repro.core.unified import PagedStatePlan
from repro.runtime.residency import (
    BlockOut,
    StateResidency,
    _block_wave,
    _LazyJit,
    decode_and_wait,
    scoped_call,
    state_buffer_aval,
)

# Donated argument positions for the paged jits (the page table rides
# LAST and is never donated — it is a tiny int32 input the host mutates
# between dispatches).
PAGED_DECODE_DONATE = (2,)  # (params, tokens, BUF, pos, active, pages)
PAGED_RESET_DONATE = (0,)  # (BUF, keep, pages)
PAGED_BLOCK_DONATE = (1,)  # (params, BUF, tokens, pos, active, ..., pages)


class PagedOutOfPagesError(RuntimeError):
    """Admission would exceed the page pool. Carries the numbers a
    caller needs to decide (wait for retirements vs reject): pages the
    request needs, pages currently free, pages live across active slots,
    and the bucket's total pool size."""

    def __init__(
        self,
        *,
        pages_needed: int,
        pages_free: int,
        pages_live: int,
        pages_total: int,
    ):
        self.pages_needed = pages_needed
        self.pages_free = pages_free
        self.pages_live = pages_live
        self.pages_total = pages_total
        super().__init__(
            f"paged admission refused: request needs {pages_needed} "
            f"page(s) but only {pages_free} of the bucket's {pages_total} "
            f"pool pages are free ({pages_live} live across active slots)"
        )


class PagedStateResidency(StateResidency):
    """The :class:`~repro.runtime.residency.StateResidency` binding with
    page-table addressing: the buffer is ``n_pages_total`` rows of one
    physical page each (null page at row 0), and every (slot, leaf) cell
    is reached by gathering the slot's table row instead of a static
    ``slot * slot_stride`` base.

    Binding validation is inherited wholesale — the logical layout IS
    the symmetric plan's, so path/dtype/per-slot-byte checks are
    unchanged."""

    def __init__(
        self,
        state_plan: PagedStatePlan,
        template: Any,
        *,
        n_slots: int,
        layout: Any | None = None,
    ):
        if not isinstance(state_plan, PagedStatePlan):
            raise TypeError(
                f"PagedStateResidency needs a PagedStatePlan, got "
                f"{type(state_plan).__name__}"
            )
        super().__init__(state_plan, template, n_slots=n_slots, layout=layout)
        self.paged_plan = state_plan
        if state_plan.slot_stride > (
            state_plan.pages_per_slot * state_plan.page_size
        ):
            raise ValueError(
                "page table does not cover the slot region: "
                f"{state_plan.pages_per_slot} x {state_plan.page_size} B "
                f"< stride {state_plan.slot_stride} B"
            )
        # page_offsets are distinct page-aligned offsets inside the
        # physical buffer (validated at plan time), i.e. a permutation
        # of physical indices 1..n_pages_pool — the table stores these
        # physical indices directly
        phys = sorted(o // state_plan.page_size for o in state_plan.page_offsets)
        if phys != list(range(1, state_plan.n_pages_pool + 1)):
            raise ValueError(
                "paged plan's page offsets do not tile the physical pool"
            )
        if state_plan.page_size % self.dtype.itemsize:
            raise ValueError(
                f"page size {state_plan.page_size} B is not a whole number "
                f"of {self.dtype.name} elements"
            )
        self.page_elems = state_plan.page_size // self.dtype.itemsize

    @property
    def phys_total_size(self) -> int:
        return self.paged_plan.phys_total_size

    def init_buffer(self, caches: Any = None):
        """A fresh physical buffer: the null page + the whole pool,
        zeroed (the models' ``init_cache`` contract is all-zero state —
        and with an all-zero table every logical read resolves to the
        null page anyway). Must be a device-OWNED buffer (``jnp.zeros``,
        like the symmetric arena) — ``device_put`` of a host array can
        zero-copy alias numpy-owned memory on CPU, which is unsafe to
        donate through the decode jits."""
        if caches is not None:
            raise ValueError(
                "paged residency initializes zero state only (allocate "
                "pages, then pack through the table)"
            )
        aval = state_buffer_aval(self.paged_plan)
        return jnp.zeros(aval.shape, aval.dtype)

    def _leaf_span(self, views) -> tuple[int, int]:
        """(element offset, element count) of a leaf inside a slot's
        logical region (slot 0's view offset == the leaf offset)."""
        item = self.dtype.itemsize
        return views[0].offset // item, views[0].used_nbytes // item

    def unpack(self, buf, pages) -> Any:
        """The cache pytree gathered through the page tables: ONE
        ``jnp.take`` of every slot's page rows, then each (slot, leaf)
        cell is a static slice + reshape of the slot's flat region. Its
        operations carry the name scope ``state.unpack``."""
        return scoped_call("state.unpack", self._unpack, buf, pages)

    def _unpack(self, buf, pages) -> Any:
        pps = self.paged_plan.pages_per_slot
        rows = jnp.take(buf, pages.reshape(-1), axis=0)
        regions = [
            rows[s * pps : (s + 1) * pps].reshape(-1)
            for s in range(self.n_slots)
        ]
        out = []
        for _path, axis, per_slot_shape, _dt, views in self._bindings:
            off, n = self._leaf_span(views)
            out.append(jnp.stack(
                [r[off : off + n].reshape(per_slot_shape) for r in regions],
                axis=axis,
            ))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def pack(self, caches: Any, buf, pages):
        """Scatter a cache pytree back through the page tables; returns
        the successor buffer value. Each slot's flat region is its
        leaves in plan order with zeros in the alignment gaps and the
        tail. Rows of unmapped logical pages all target the null page
        and provably carry zeros (see module docstring), so the
        duplicate scatter indices there are benign — and the null page
        stays all-zero by the same argument. Its operations carry the
        name scope ``state.pack``."""
        if jax.tree_util.tree_structure(caches) != self.treedef:
            raise ValueError(
                "decode returned a cache pytree with a different structure "
                "than the bound template"
            )
        return scoped_call("state.pack", self._pack, caches, buf, pages)

    def _pack(self, caches: Any, buf, pages):
        pps = self.paged_plan.pages_per_slot
        spans = sorted(
            (
                self._leaf_span(views) + (leaf, axis)
                for leaf, (_path, axis, _pss, _dt, views) in zip(
                    jax.tree_util.tree_leaves(caches), self._bindings
                )
            ),
            key=lambda span: span[0],
        )
        slot_rows = []
        for s in range(self.n_slots):
            pieces, end = [], 0
            for off, n, leaf, axis in spans:
                pieces.append(jnp.zeros((off - end,), self.dtype))
                pieces.append(
                    jax.lax.index_in_dim(leaf, s, axis, keepdims=False)
                    .reshape(-1)
                )
                end = off + n
            pieces.append(jnp.zeros((pps * self.page_elems - end,), self.dtype))
            slot_rows.append(
                jnp.concatenate(pieces).reshape(pps, self.page_elems)
            )
        return buf.at[pages.reshape(-1)].set(jnp.concatenate(slot_rows))


# ------------------------------------------------- jitted decode functions
#
# Module-level factories, same discipline as runtime/residency.py: the
# serving backend, the AOT compiler (runtime/aot.py) and the static
# decode lint all lower THESE functions. The page table is the LAST
# positional argument of every one.


def paged_decode_impl(model, residency: PagedStateResidency) -> Callable:
    """One decode wave through the page tables:
    ``(params, tokens, buf, pos, active, pages) -> (logits, buf')``."""

    def decode_step(params, tokens, buf, pos, active, pages):
        caches = residency.unpack(buf, pages)
        logits, new_caches = model.decode_step(
            params, tokens, caches, pos, active=active
        )
        return logits, residency.pack(new_caches, buf, pages)

    return decode_step


def paged_reset_impl(model, residency: PagedStateResidency) -> Callable:
    """Slot reset through the page tables:
    ``(buf, keep, pages) -> buf'`` — zeroes every page the dropped
    slots still map (stale mappings included: the symmetric baseline
    wipes the whole slot region at admit, and so does this)."""

    def reset_slots(buf, keep, pages):
        caches = residency.unpack(buf, pages)
        return residency.pack(model.reset_slots(caches, keep), buf, pages)

    return reset_slots


def paged_block_impl(
    model, residency: PagedStateResidency, sampler, length: int
) -> Callable:
    """``length`` decode waves in one ``lax.scan``: gather the cache
    pytree through the tables ONCE, scan the waves over the pytree
    carry, scatter back ONCE. ``pack``/``unpack`` are exact inverses on
    values, so this is wave-for-wave identical to the symmetric block's
    per-wave pack/unpack — with a 1/length page-indirection cost."""

    def decode_block(params, buf, tokens, pos, active, done, budget, keys,
                     eos, pages):
        caches0 = residency.unpack(buf, pages)

        def body(carry, _):
            caches, tokens, pos, done, budget, keys = carry
            caches, (tokens, pos, done, budget, keys), out = (
                _block_wave(model, sampler, params, caches, tokens,
                            pos, active, done, budget, keys, eos)
            )
            return (caches, tokens, pos, done, budget, keys), out

        carry, (toks, emitted) = jax.lax.scan(
            body, (caches0, tokens, pos, done, budget, keys), None,
            length=length,
        )
        caches, tokens, pos, done, budget, keys = carry
        buf = residency.pack(caches, buf, pages)
        return (buf, tokens, pos, done, budget, keys), toks, emitted

    return decode_block


class PagedResidentState:
    """Serving backend: the donated flat buffer addressed through
    per-slot page tables, with allocate-on-admit / free-on-retire page
    bookkeeping.

    Same decode/reset/decode_block interface as
    :class:`~repro.runtime.residency.ResidentState` (the engine is
    oblivious to the indirection), plus the page lifecycle the engine's
    admission path drives: :meth:`allocate_slot` before a slot is
    reset/prefilled, :meth:`free_slot` when it retires."""

    residency = True
    paged = True

    def __init__(
        self,
        model,
        residency: PagedStateResidency,
        *,
        executables: "dict[str, Any] | None" = None,
    ):
        self.model = model
        self._residency = residency
        plan = residency.paged_plan
        self.plan = plan
        self.buf = residency.init_buffer()
        # host-authoritative page table, mirrored to device only when a
        # mapping actually changes (admission); 0 = null page
        self._table = np.zeros(
            (residency.n_slots, plan.pages_per_slot), np.int32
        )
        self._table_dev = jnp.array(self._table)
        # free pool as physical page indices (ascending — deterministic
        # assignment order), page -> (slot, logical_idx) for EVERY
        # mapped page (live or stale), and the live set: pages held by
        # currently-active slots
        self._free: list[int] = sorted(
            o // plan.page_size for o in plan.page_offsets
        )
        self._owner: dict[int, tuple[int, int]] = {}
        self._live: set[int] = set()
        self._page_admit: dict[int, int] = {}  # page -> admitted wave
        self._slot_rid: dict[int, int] = {}
        # page occupancy intervals, the page-granular twin of the
        # engine's slot_log: (page, admitted_wave, finished_wave, rid)
        self.page_log: list[tuple[int, int, int, int]] = []
        self.pages_live_peak = 0
        self._execs = executables or {}
        self._decode = self._execs.get("paged_decode") or _LazyJit(
            paged_decode_impl(model, residency),
            donate_argnums=PAGED_DECODE_DONATE,
        )
        self._reset = self._execs.get("paged_reset") or _LazyJit(
            paged_reset_impl(model, residency),
            donate_argnums=PAGED_RESET_DONATE,
        )
        self._block_jits: dict[int, Any] = {}  # scan length -> callable

    # ------------------------------------------------- page lifecycle
    @property
    def pages_total(self) -> int:
        return self.plan.n_pages_pool

    @property
    def pages_live(self) -> int:
        return len(self._live)

    def slot_pages(self, slot: int) -> list[int]:
        """The physical pages ``slot`` holds LIVE (mapped and counted
        against the pool; stale mappings of a retired slot excluded)."""
        return sorted(
            int(p) for p in self._table[slot] if p and int(p) in self._live
        )

    def allocate_slot(
        self, slot: int, needed_len: int, *, rid: int, wave: int
    ) -> int:
        """Map the pages ``slot`` needs to serve a request whose cache
        never grows past ``needed_len`` rows. Returns the number of
        pages now live for the slot; raises :class:`PagedOutOfPagesError`
        (mutating NOTHING) when the free pool cannot cover the need.

        Assignment order is the byte-identity ladder from the module
        docstring: the slot's own stale pages first, never-mapped free
        pages next, stolen stale pages of other retired slots last —
        each group in ascending physical order, so runs are
        deterministic."""
        need = self.plan.pages_needed(needed_len)
        free_set = set(self._free)
        assigned: dict[int, int] = {}
        for j in need:
            p = int(self._table[slot, j])
            if p and p in free_set:  # (1) stale-self: still mapped here
                assigned[j] = p
                free_set.discard(p)
        remaining = [j for j in need if j not in assigned]
        avail = sorted(free_set)
        pool = [p for p in avail if p not in self._owner] + [
            p for p in avail if p in self._owner
        ]
        if len(remaining) > len(pool):
            raise PagedOutOfPagesError(
                pages_needed=len(need),
                pages_free=len(self._free),
                pages_live=len(self._live),
                pages_total=self.plan.n_pages_pool,
            )
        dirty = False
        for j, p in zip(remaining, pool):
            old = self._owner.get(p)
            if old is not None:  # (3) steal: clear the stale owner's map
                self._table[old[0], old[1]] = 0
            self._table[slot, j] = p
            self._owner[p] = (slot, j)
            assigned[j] = p
            dirty = True
        taken = set(assigned.values())
        self._free = sorted(set(self._free) - taken)
        for p in taken:
            self._live.add(p)
            self._page_admit[p] = wave
        self._slot_rid[slot] = rid
        self.pages_live_peak = max(self.pages_live_peak, len(self._live))
        if dirty:
            self._table_dev = jnp.array(self._table)
        return len(taken)

    def free_slot(self, slot: int, wave: int) -> list[int]:
        """Return a retired slot's live pages to the free pool and log
        their occupancy intervals. The table row is NOT cleared (lazy
        invalidation — see module docstring), so the device table needs
        no refresh and the retired slot's stale bytes stay readable,
        exactly like the symmetric baseline's."""
        released = self.slot_pages(slot)
        rid = self._slot_rid.get(slot, -1)
        for p in released:
            self._live.discard(p)
            self.page_log.append((p, self._page_admit.pop(p), wave, rid))
        self._free = sorted(set(self._free) | set(released))
        return released

    # ------------------------------------------------------- serving
    def decode(self, params, tokens, pos, active):
        logits, self.buf = decode_and_wait(
            self._decode, params, tokens, self.buf, pos, active,
            self._table_dev,
        )
        return logits

    def reset(self, keep):
        self.buf = self._reset(self.buf, jnp.array(keep), self._table_dev)
        jax.block_until_ready(self.buf)

    def decode_block(self, params, tokens, pos, active, done, budget, keys,
                     eos, *, length, sampler) -> BlockOut:
        """Scan-block decode through the page tables — the contract of
        :meth:`~repro.runtime.residency.ResidentState.decode_block`.
        Table mutations happen only at admission and the engine chains
        blocks only when nothing is queued, so an in-flight block always
        holds the current table."""
        jitted = self._block_jits.get(length)
        if jitted is None:
            jitted = self._execs.get(block_entry_name("paged", length))
            if jitted is None:
                jitted = _LazyJit(
                    paged_block_impl(
                        self.model, self._residency, sampler, length
                    ),
                    donate_argnums=PAGED_BLOCK_DONATE,
                )
            self._block_jits[length] = jitted
        carry, toks, emitted = jitted(
            params, self.buf, tokens, pos, active, done, budget, keys, eos,
            self._table_dev,
        )
        self.buf, tokens, pos, done, budget, keys = carry
        return BlockOut(tokens=tokens, pos=pos, done=done, budget=budget,
                        keys=keys, wave_tokens=toks, emitted=emitted)

    @property
    def caches(self) -> Any:
        """The cache pytree gathered through the live page tables
        (inspection only; the serving path never materializes this)."""
        return self._residency.unpack(self.buf, self._table_dev)

    @property
    def live_bytes(self) -> int:
        """Pool bytes holding live state — the paged win the report and
        benches track: ``pages_live * page_size``, vs the symmetric
        backend's constant ``StatePlan.total_size``."""
        return len(self._live) * self.plan.page_size

    @property
    def allocated_bytes(self) -> int:
        """The physical buffer allocation (null page + whole pool)."""
        return int(self.buf.nbytes)
