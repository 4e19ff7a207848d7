"""Flat memory arena materializing an Offset Calculation plan (paper §5).

One ``bytearray``-backed numpy buffer of ``total_size`` bytes; every
intermediate tensor is a zero-copy view at its planned offset. This is the
TFLite-style deployment of the paper's result: allocate once, reuse across
the whole inference — and across inferences.

The arena is deliberately decoupled from the planner: it consumes an
:class:`ArenaLayout` (offsets + per-tensor slot sizes + total), which can
come from a freshly computed :class:`~repro.core.planner.MemoryPlan`,
straight from a precompiled :class:`~repro.core.artifact.PlanBundle`'s
stored offsets, or from the cross-step
:class:`~repro.core.unified.StatePlan` (slot/KV layout) — both arenas of
a :class:`~repro.core.unified.UnifiedPlan` materialize from that one
object (:meth:`ArenaLayout.from_unified`). The serving path never needs
planner objects to materialize its memory.

Two arena implementations share the layout contract: the numpy
:class:`Arena` (host buffers — the executor's deployment path) and the
jax :class:`DeviceArena` (one flat device buffer of the state's element
dtype whose views are carved with ``lax.dynamic_slice`` + reshape — the
engine's cross-step state residency, see ``runtime/residency.py``).
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Mapping

import numpy as np

if TYPE_CHECKING:
    from repro.core.artifact import PlanBundle
    from repro.core.planner import MemoryPlan
    from repro.core.unified import StatePlan, UnifiedPlan


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Everything an arena needs: where each tensor lives and how big the
    buffer is. ``sizes`` are the *planned slot* sizes (alignment-rounded)
    used for bounds enforcement."""

    total_size: int
    offsets: Mapping[int, int]  # tensor_id -> byte offset
    sizes: Mapping[int, int]  # tensor_id -> planned slot bytes

    @staticmethod
    def from_plan(plan: "MemoryPlan") -> "ArenaLayout":
        return ArenaLayout(
            total_size=plan.total_size,
            offsets=dict(plan.offsets),
            sizes={r.tensor_id: r.size for r in plan.records},
        )

    @staticmethod
    def from_bundle(bundle: "PlanBundle") -> "ArenaLayout":
        """Materialize straight from a plan artifact's stored offsets."""
        return ArenaLayout.from_plan(bundle.plan)

    @staticmethod
    def from_state_plan(state: "StatePlan | None") -> "ArenaLayout":
        """Cross-step state arena: one dense tensor id per (slot, leaf)
        pair (``slot * n_leaves + leaf_index``), addressed through the
        plan's :meth:`~repro.core.unified.StatePlan.leaf_view_spec` — the
        same spec the device arena and the residency views consume.

        Unlike activation layouts, state regions must be pairwise
        DISJOINT (every slot's state is live across the whole decode), so
        this constructor validates non-overlap in addition to bounds."""
        if state is None:
            raise ValueError(
                "no cross-step state plan to materialize (state_plan is "
                "None — a v1 bundle ships only the activation half; "
                "recompile with launch/compile.py for a v2 bundle)"
            )
        offsets: dict[int, int] = {}
        sizes: dict[int, int] = {}
        for view in state.leaf_view_spec():
            offsets[view.tensor_id] = view.offset
            sizes[view.tensor_id] = view.slot_nbytes
        layout = ArenaLayout(
            total_size=state.total_size, offsets=offsets, sizes=sizes
        )
        layout.validate()
        layout.validate_disjoint()
        return layout

    @staticmethod
    def from_unified(
        plan: "UnifiedPlan",
    ) -> "tuple[ArenaLayout | None, ArenaLayout | None]":
        """Both arenas from one object: (activation, cross-step state)."""
        return plan.arena_layouts()

    def validate(self) -> None:
        """Every planned slot must lie inside the buffer — a corrupt or
        hand-edited artifact fails here, before any bytes are aliased."""
        for tid, off in self.offsets.items():
            size = self.sizes.get(tid, 0)
            if off < 0 or off + size > self.total_size:
                raise ValueError(
                    f"tensor {tid}: slot [{off}, {off + size}) outside "
                    f"arena of {self.total_size} B"
                )

    def validate_disjoint(self) -> None:
        """No two planned slots may share bytes. Activation layouts alias
        on purpose (disjoint lifetimes sharing memory IS the paper's
        win), so this is NOT part of :meth:`validate`; cross-step state
        regions are all live at once and must never overlap — a corrupt
        state plan fails here with the offending pair named."""
        spans = sorted(
            (off, off + self.sizes.get(tid, 0), tid)
            for tid, off in self.offsets.items()
        )
        for (s1, e1, t1), (s2, e2, t2) in zip(spans, spans[1:]):
            if s2 < e1:
                raise ValueError(
                    f"state regions overlap: tensor {t1} [{s1}, {e1}) and "
                    f"tensor {t2} [{s2}, {e2}) share bytes"
                )


class Arena:
    def __init__(self, layout: "ArenaLayout | MemoryPlan"):
        if not isinstance(layout, ArenaLayout):
            layout = ArenaLayout.from_plan(layout)
        layout.validate()
        self.layout = layout
        self.buf = np.zeros(max(layout.total_size, 1), dtype=np.uint8)
        self._sizes = layout.sizes

    @property
    def nbytes(self) -> int:
        return self.buf.nbytes

    def store(self, tensor_id: int, value: np.ndarray) -> np.ndarray:
        """Copy ``value``'s bytes to the tensor's planned slot; return a
        view aliasing arena memory (C-contiguous, same shape/dtype)."""
        off = self.layout.offsets[tensor_id]
        raw = np.ascontiguousarray(value)
        nbytes = raw.nbytes
        if nbytes > self._sizes[tensor_id]:
            raise ValueError(
                f"tensor {tensor_id}: {nbytes} B exceeds planned "
                f"{self._sizes[tensor_id]} B"
            )
        dst = self.buf[off : off + nbytes]
        dst[:] = raw.reshape(-1).view(np.uint8)
        return self.view(tensor_id, raw.shape, raw.dtype)

    def view(self, tensor_id: int, shape, dtype) -> np.ndarray:
        off = self.layout.offsets[tensor_id]
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        # a too-large view would silently alias the NEXT tensor's planned
        # slot — enforce both the per-tensor slot size and the arena end
        if nbytes > self._sizes[tensor_id]:
            raise ValueError(
                f"tensor {tensor_id}: view of {nbytes} B exceeds planned "
                f"{self._sizes[tensor_id]} B"
            )
        if off + nbytes > self.buf.nbytes:
            raise ValueError(
                f"tensor {tensor_id}: view [{off}, {off + nbytes}) exceeds "
                f"arena of {self.buf.nbytes} B"
            )
        return (
            self.buf[off : off + nbytes]
            .view(np.dtype(dtype))
            .reshape(shape)
        )


class DeviceArena:
    """jax twin of :class:`Arena`: the same :class:`ArenaLayout` and the
    same bounds-checked view contract over a flat device buffer, which
    the engine's decode step donates and updates in place, keeping the
    whole cross-step state in ONE device buffer across waves (the
    decode step writes it through ``models/state_view.py``).

    The buffer is typed by the one element dtype every tensor it holds
    shares (a model's cache leaves all carry ``cfg.dtype``), and byte
    offsets become element offsets. Views are plain slices and reshapes:
    no byte view and no bitcast, whose ``(..., itemsize)`` minor
    dimension the TPU pads to 128 lanes. A view of another dtype is
    refused.

    All offsets/sizes are Python ints (the plan is static), so every
    ``dynamic_slice`` lowers to a static-index slice XLA can fuse.
    """

    def __init__(self, layout: "ArenaLayout", dtype):
        layout.validate()
        self.layout = layout
        self.dtype = np.dtype(dtype)
        self._sizes = layout.sizes
        item = self.dtype.itemsize
        unaligned = sorted(
            tid for tid, off in layout.offsets.items() if off % item
        )
        if layout.total_size % item or unaligned:
            raise ValueError(
                f"a {self.dtype.name} device arena needs offsets and a "
                f"total size divisible by {item} B (tensors "
                f"{unaligned[:3]}, total {layout.total_size} B)"
            )

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    @property
    def size(self) -> int:
        """Buffer length in elements."""
        return self.length(self.layout.total_size, self.dtype)

    @staticmethod
    def length(total_size: int, dtype) -> int:
        """Elements of the flat buffer that holds ``total_size`` bytes of
        ``dtype`` state, at least one. :meth:`allocate` and
        ``residency.state_buffer_aval`` both shape the buffer from here."""
        return max(total_size // np.dtype(dtype).itemsize, 1)

    def allocate(self):
        """A fresh zeroed device buffer of the arena's full size."""
        import jax.numpy as jnp

        return jnp.zeros((self.size,), self.dtype)

    def _check(self, tensor_id: int, shape, dtype) -> tuple[int, int]:
        """(element offset, element count) of a ``shape``/``dtype`` view
        of the tensor's planned slot, after the bounds contract."""
        if np.dtype(dtype) != self.dtype:
            raise ValueError(
                f"tensor {tensor_id}: a {np.dtype(dtype).name} view of a "
                f"{self.dtype.name} device arena (the arena holds one "
                f"element dtype and never bitcasts)"
            )
        off = self.layout.offsets[tensor_id]
        count = int(np.prod(shape))
        nbytes = count * self.dtype.itemsize
        # same contract as Arena.view: an oversized view would silently
        # alias the NEXT tensor's planned slot
        if nbytes > self._sizes[tensor_id]:
            raise ValueError(
                f"tensor {tensor_id}: view of {nbytes} B exceeds planned "
                f"{self._sizes[tensor_id]} B"
            )
        if off + nbytes > self.layout.total_size:
            raise ValueError(
                f"tensor {tensor_id}: view [{off}, {off + nbytes}) exceeds "
                f"arena of {self.layout.total_size} B"
            )
        return off // self.dtype.itemsize, count

    def view(self, buf, tensor_id: int, shape, dtype):
        """Read the tensor's planned slot out of ``buf`` as a
        ``shape``/``dtype`` jax array (slice + reshape)."""
        import jax

        off, count = self._check(tensor_id, shape, dtype)
        return jax.lax.dynamic_slice(buf, (off,), (count,)).reshape(shape)
