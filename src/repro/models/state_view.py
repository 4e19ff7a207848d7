"""Decode state read and written in place in a slot-major buffer.

A decode step takes its cache either as a pytree of arrays, each leaf
carrying the slot batch (the step returns new leaves), or as a
:class:`SlotBuffer`: the flat buffer a state plan lays out as
``n_slots`` regions of ``slot_elems`` elements, and where in a region
each cache leaf lives. Through a SlotBuffer each layer reads its state
as a view of the buffer and writes back only what it changed: one K and
one V row per active slot in an attention layer (:func:`write_rows`),
the new conv and SSM state in a Mamba layer (:func:`write`). The
model's code is the same for both forms: :func:`read`, :func:`write` and
:func:`write_rows` take either a leaf array or a :class:`LeafView`.

Reads run under the name scope ``state.unpack`` and writes under
``state.pack``, so a profile counts whatever moving of state is left.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp


def scoped_call(scope: str, fn: Callable, *args) -> Any:
    """``fn(*args)`` as a call of its own in the lowered program, its
    operations under the name scope ``scope`` (a profiler trace shows it
    in each op's ``tf_op`` path). A name scope alone lives only in the
    ops' metadata, which JAX's persistent compile cache leaves out of
    its key, so a program cached before the scope existed would be found
    and served without it; the call is part of the program text the key
    hashes. XLA inlines the call."""
    with jax.named_scope(scope):
        return jax.jit(fn)(*args)


# Elements zeroed per step of a slot reset (8 MiB of bf16).
ZERO_PIECE = 1 << 22


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one cache leaf lives in every slot region: consecutive
    layers (one per period for a leaf stacked over the periods, else
    one) of ``shape`` each (one slot's share of one layer), starting
    ``col`` elements into the region."""

    col: int
    shape: tuple[int, ...]

    @property
    def size(self) -> int:
        return math.prod(self.shape)


class SlotBuffer:
    """The state buffer as one decode trace reads and writes it.

    ``buf`` is the flat buffer's current value and ``slots`` a pytree of
    the cache's structure whose leaves are :class:`LeafSlot`\\ s. Each
    write replaces ``buf``, so the object holds the state of one trace:
    a loop body sets ``buf`` to its carry and hands back the final value.

    The buffer is seen as ``(n_slots, slot_elems / lane, lane)``: a layer
    of a leaf across all slots is then a range of its middle axis, and
    one token row of a slot a range of one of its rows. ``lane`` is the
    largest divisor of 128 that divides the region, every leaf's offset,
    layer and row. On the TPU a flat bf16 array is stored in tiles of
    1024 elements, which are the ``(8, 128)`` tiles of an array whose
    last axis is 128, so with ``lane`` 128 and regions of whole tiles the
    view and a layer's range of it are the buffer itself, not a copy."""

    def __init__(self, buf, n_slots: int, slot_elems: int, slots: Any):
        self.buf = buf
        self.n_slots = n_slots
        self.slot_elems = slot_elems
        self.slots = slots
        self.lane = math.gcd(
            128, slot_elems,
            *(g for s in jax.tree_util.tree_leaves(slots)
              for g in (s.col, s.size, s.size // s.shape[0])),
        )

    def views(self, slots: Any, layer) -> Any:
        """``slots`` (a subtree of :attr:`slots`) with each leaf the
        :class:`LeafView` of layer ``layer``."""
        return jax.tree_util.tree_map(
            lambda s: LeafView(self, s, layer), slots
        )

    def _rows(self, buf):
        return buf.reshape(self.n_slots, -1, self.lane)

    def _first_row(self, slot: LeafSlot, layer):
        """Row of the view where ``layer`` of the leaf starts."""
        return slot.col // self.lane + layer * (slot.size // self.lane)

    def rows(self, slot: LeafSlot, layer):
        """The buffer as its ``(n_slots, slot_elems / lane, lane)`` view
        and the row where ``layer`` of the leaf starts: what a kernel
        that reads the leaf where it lies is given. No copy is made and
        nothing is scoped: the reader's own ops are not state moved."""
        return self._rows(self.buf), self._first_row(slot, layer)

    def read(self, slot: LeafSlot, layer):
        """``layer`` of the leaf across all slots, ``(n_slots, *shape)``."""

        def view(buf):
            rows = jax.lax.dynamic_slice_in_dim(
                self._rows(buf), self._first_row(slot, layer),
                slot.size // self.lane, axis=1,
            )
            return rows.reshape(self.n_slots, *slot.shape)

        return scoped_call("state.unpack", view, self.buf)

    def write(self, slot: LeafSlot, layer, value) -> None:
        """Store ``value`` (``(n_slots, *shape)``) as ``layer`` of the
        leaf in every slot."""

        def store(buf, value):
            rows = jax.lax.dynamic_update_slice_in_dim(
                self._rows(buf),
                value.reshape(self.n_slots, -1, self.lane),
                self._first_row(slot, layer), axis=1,
            )
            return rows.reshape(-1)

        self.buf = scoped_call("state.pack", store, self.buf, value)

    def write_rows(self, slot: LeafSlot, layer, index, rows) -> None:
        """Store ``rows[b]`` at row ``index[b]`` of the leaf's first axis
        (its token axis) in slot ``b``; an index outside ``[0,
        shape[0])`` writes nothing, as ``mode="drop"`` does. One update
        in place per slot: a scatter into the whole buffer would become
        a loop of them."""
        span = slot.size // slot.shape[0] // self.lane  # view rows per row

        def store(buf, index, rows):
            view = self._rows(buf)
            inside = (index >= 0) & (index < slot.shape[0])
            first = self._first_row(slot, layer) + jnp.where(
                inside, index, 0) * span
            rows = rows.reshape(self.n_slots, 1, span, self.lane)
            for b in range(self.n_slots):
                at = (b, first[b], 0)
                old = jax.lax.dynamic_slice(view, at, (1, span, self.lane))
                view = jax.lax.dynamic_update_slice(
                    view, jnp.where(inside[b], rows[b], old), at
                )
            return view.reshape(-1)

        self.buf = scoped_call("state.pack", store, self.buf, index,
                               rows.astype(self.buf.dtype))

    def zero_slots(self, keep) -> None:
        """Zero the whole region of each slot ``b`` with ``keep[b]``
        False; every other element is left as it is. One loop over the
        pieces of just those regions: a branch per slot would copy the
        whole buffer in its untaken arm, and zeros the size of a region
        would be a temporary that large."""
        piece = min(self.slot_elems, ZERO_PIECE)
        n_pieces = -(-self.slot_elems // piece)

        def zero(buf, keep):
            reset = jnp.argsort(keep, stable=True)  # slots to zero first

            def body(i, buf):
                # the last piece of a region ends at the region's end
                start = jnp.minimum(i % n_pieces * piece,
                                    self.slot_elems - piece)
                return jax.lax.dynamic_update_slice(
                    buf, jnp.zeros((piece,), buf.dtype),
                    (reset[i // n_pieces] * self.slot_elems + start,),
                )

            return jax.lax.fori_loop(0, jnp.sum(~keep) * n_pieces, body, buf)

        self.buf = scoped_call("state.pack", zero, self.buf, keep)


@dataclasses.dataclass(frozen=True)
class LeafView:
    """One layer of one cache leaf, as a view of a :class:`SlotBuffer`."""

    state: SlotBuffer
    slot: LeafSlot
    layer: Any  # int, or the layer loop's traced index

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.state.n_slots, *self.slot.shape)


def read(leaf):
    """The leaf's value: an array as it is, a view read from its buffer."""
    if isinstance(leaf, LeafView):
        return leaf.state.read(leaf.slot, leaf.layer)
    return leaf


def write(leaf, value):
    """The leaf after it is replaced by ``value``: ``value`` itself for
    an array leaf, the view (now over the stored value) for a view."""
    if isinstance(leaf, LeafView):
        leaf.state.write(leaf.slot, leaf.layer, value)
        return leaf
    return value


def write_rows(leaf, index, rows):
    """The leaf after ``rows[b]`` is set at ``[b, index[b]]``; an index
    past axis 1 drops the row (``.at[...].set(mode="drop")``)."""
    if isinstance(leaf, LeafView):
        leaf.state.write_rows(leaf.slot, leaf.layer, index, rows)
        return leaf
    b = jnp.arange(leaf.shape[0])
    return leaf.at[b, index].set(rows, mode="drop")
