"""Paged state: the residency buffer addressed through per-slot page
tables (ROADMAP open item 2 — §4 shared objects at page granularity).

The symmetric :class:`~repro.runtime.residency.StateResidency` gives
every slot a full ``max_len`` region, so a 64-token request in a
4096-token bucket strands ~98% of its planned state bytes. This module
keeps the *logical* layout identical — the same
:class:`~repro.core.unified.StatePlan` leaves, offsets and strides the
whole codebase reasons about — but backs it with a pool of fixed-size
physical pages (:class:`~repro.core.unified.PagedStatePlan`):

* :class:`PagedStateResidency` is the paged addressing of the one
  serving backend (:class:`~repro.runtime.residency.ResidentState`): a
  program enters the state by gathering each slot's logical region from
  its table row (``unpack``, ``jnp.take`` over the buffer's page rows)
  and leaves it by scattering it back (``pack``) — one gather + one
  scatter per program, all shapes static, so the program stays fixed
  and the table, its last argument, is plain int32 *data* (no retrace,
  no recompile when the mapping changes);
* physical page 0 is the reserved all-zero **null page**: unmapped
  logical pages read as zeros through it, and every scatter row aimed
  at it provably carries zeros (unmapped bytes are zeros on the way in
  and the decode masks its cache updates by ``active``), so duplicate
  scatter indices are benign;
* :class:`PagePool`, owned by the addressing, is the serving-time
  bookkeeping the backend's ``admit``/``retire`` hooks drive:
  allocate-on-admit (:meth:`~PagePool.allocate_slot` maps the pages a
  request's ``needed_len`` intersects, refusing with
  :class:`PagedOutOfPagesError` when the pool cannot cover it) and
  free-on-retire (:meth:`~PagePool.free_slot`), with a page log
  mirroring the engine's slot log for the §4-style audit
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

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.unified import PagedStatePlan
from repro.runtime.residency import Addressing, StateResidency, scoped_call


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


class PagePool:
    """The physical pages of a paged plan and which slot holds each, for
    one engine: the host-authoritative page table (mirrored to the
    device as ``table_dev`` only when a mapping changes, at admission),
    the free list, each mapped page's owner, and the page log."""

    def __init__(self, plan: PagedStatePlan, n_slots: int):
        self.plan = plan
        # 0 = the null page
        self.table = np.zeros((n_slots, plan.pages_per_slot), np.int32)
        self.table_dev = jnp.array(self.table)
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
        self.log: list[tuple[int, int, int, int]] = []
        self.peak = 0  # most pages live at once

    @property
    def total(self) -> int:
        return self.plan.n_pages_pool

    @property
    def live(self) -> int:
        return len(self._live)

    def slot_pages(self, slot: int) -> list[int]:
        """The physical pages ``slot`` holds LIVE (mapped and counted
        against the pool; stale mappings of a retired slot excluded)."""
        return sorted(
            int(p) for p in self.table[slot] if p and int(p) in self._live
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
            p = int(self.table[slot, j])
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
                self.table[old[0], old[1]] = 0
            self.table[slot, j] = p
            self._owner[p] = (slot, j)
            assigned[j] = p
            dirty = True
        taken = set(assigned.values())
        self._free = sorted(set(self._free) - taken)
        for p in taken:
            self._live.add(p)
            self._page_admit[p] = wave
        self._slot_rid[slot] = rid
        self.peak = max(self.peak, len(self._live))
        if dirty:
            self.table_dev = jnp.array(self.table)
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
            self.log.append((p, self._page_admit.pop(p), wave, rid))
        self._free = sorted(set(self._free) | set(released))
        return released


class PagedStateResidency(StateResidency):
    """The :class:`~repro.runtime.residency.StateResidency` binding with
    page-table addressing: the buffer is ``n_pages_total`` rows of one
    physical page each (null page at row 0), and every (slot, leaf) cell
    is reached by gathering the slot's table row instead of a static
    ``slot * slot_stride`` base. A program enters the state as the
    gathered cache pytree, which the model takes as the reference does,
    and leaves it by scattering that back; the page table of its
    :class:`PagePool` is the program's last argument.

    Binding validation is inherited wholesale — the logical layout IS
    the symmetric plan's, so path/dtype/per-slot-byte checks are
    unchanged."""

    prefix = "paged"

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
        self.pool = PagePool(state_plan, n_slots)

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
        return jnp.zeros(self.aval.shape, self.aval.dtype)

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

    # a program gathers the cache pytree through the tables on entry and
    # scatters it back on leaving; the model takes the pytree, as it
    # takes the reference's
    enter, leave = unpack, pack
    view, unview, reset = Addressing.view, Addressing.unview, Addressing.reset

    def args(self) -> tuple:
        return (self.pool.table_dev,)

    def live_bytes(self, value) -> int:
        """Pool bytes holding live state — the paged win the report
        tracks: ``pool.live * page_size``, against the symmetric state's
        constant ``StatePlan.total_size``."""
        return self.pool.live * self.paged_plan.page_size

    def admit(self, slot: int, needed_len: int, *, rid: int,
              wave: int) -> None:
        self.pool.allocate_slot(slot, needed_len, rid=rid, wave=wave)

    def retire(self, slot: int, wave: int) -> None:
        self.pool.free_slot(slot, wave)
