"""Pallas TPU kernel: single-token decode attention that reads K and V
where they lie in the planned state buffer.

The state buffer is seen as ``(n_slots, rows, 128)`` (``models/
state_view.py`` ``SlotBuffer``): one token of a cache leaf is ``span``
consecutive rows of 128 lanes, its ``n_kv`` heads of ``head_dim`` laid
end to end. Where ``head_dim`` neither fills whole rows nor divides one
(zamba2's 224) heads straddle rows, so a ``(n_slots, T, n_kv,
head_dim)`` view of the leaf is not the buffer's own tiling and XLA
copies and relayouts the whole leaf before an einsum can read it. This
kernel reads blocks of whole token rows from the buffer instead and
splits the heads itself:

* row ``r`` of every token in a block is one strided load, and the
  ``span`` loads side by side are the block as ``(tokens, n_kv *
  head_dim)``, heads end to end along the lanes;
* the scores of every head are one matmul against the block-diagonal
  query (each query head's values in its KV head's lanes, zeros
  elsewhere), so a head never has to be cut out of its rows;
* the output is one matmul of the probabilities (in V's dtype, as the
  einsum takes them) against the V block, accumulated in f32 over the
  blocks, of which each head keeps its own lanes.

Grid ``(n_slots, 2 * n_blocks)``: the first ``n_blocks`` steps stream K
and keep every score in VMEM; the softmax is then exact, in f32, and
the last ``n_blocks`` steps stream V. Each K and V block is read once.
Every position up to ``T`` is read; positions past a slot's ``pos`` are
masked, as ``attention.attn_decode`` masks them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = -1e30
# Largest block of tokens one grid step reads, and what the blocks,
# scores and accumulator may take of the 16 MiB of scoped VMEM (the
# kernel's own temporaries take the rest).
MAX_BLOCK_T = 128
VMEM_BUDGET = 12 * 2**20


def _head_mask(n_kv: int, head_dim: int) -> np.ndarray:
    """``(n_kv, n_kv * head_dim)`` 0/1: lane ``e`` of a token row holds
    head ``e // head_dim``."""
    e = np.arange(n_kv * head_dim)
    return (e[None, :] // head_dim == np.arange(n_kv)[:, None]).astype(
        np.float32)


def _vmem_bytes(t_len: int, span: int, rows_g: int, block_t: int) -> int:
    """VMEM the kernel's operands and scratch take: K and V blocks and
    the query and head mask (at most ``rows_g`` rows), double-buffered;
    the f32 scores of the whole leaf and the f32 accumulator."""
    pad = lambda n, m: -(-n // m) * m  # noqa: E731
    width = span * LANES
    blocks = 2 * 2 * block_t * width * 2
    query_mask = 2 * pad(rows_g, 16) * width * (2 + 4)
    scores = t_len // block_t * pad(rows_g, 8) * pad(block_t, LANES) * 4
    acc = pad(rows_g, 8) * width * 4
    return blocks + query_mask + scores + acc


def block_tokens(t_len: int, span: int, first_rows, itemsize: int,
                 rows_g: int) -> int | None:
    """Tokens the kernel reads per grid step from leaves that start at
    the rows ``first_rows`` of the buffer (their first layer), each
    ``t_len`` tokens of ``span`` rows, for ``rows_g`` query rows (query
    heads) a slot; None where the kernel cannot read them in place.

    A block is whole tokens that fill whole tiles of 16-bit rows and
    starts each leaf's layers on a block boundary: the largest power of
    two up to :data:`MAX_BLOCK_T` dividing ``t_len`` and every leaf's
    first token, within :data:`VMEM_BUDGET`. The kernel reads 16-bit
    leaves whose tokens are an even number of rows (a 32-bit word holds
    two rows)."""
    if itemsize != 2 or span % 2:
        return None
    if any(r % span for r in first_rows):
        return None
    tb = MAX_BLOCK_T
    while tb >= 1:
        if (t_len % tb == 0 and tb * span % 16 == 0
                and all(r // span % tb == 0 for r in first_rows)
                and _vmem_bytes(t_len, span, rows_g, tb) <= VMEM_BUDGET):
            return tb
        tb //= 2
    return None


def _kernel(first_ref, pos_ref, qm_ref, mask_ref, k_ref, v_ref, o_ref,
            s_ref, acc_ref, *, n_blocks: int, block_t: int, span: int,
            groups: int, n_kv: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    def rows(ref):
        """The block as ``(block_t, span * 128)``: a token's rows side by
        side. Strided loads take 32-bit words, each two bf16 rows (the
        first in the low half), so word row ``i`` of every token is one
        load and its halves are rows ``2i`` and ``2i + 1``."""
        words = ref.bitcast(jnp.uint32)
        parts = []
        for i in range(span // 2):
            w = words[0, pl.ds(i, block_t, stride=span // 2), :]
            parts += [w << 16, w >> 16 << 16]
        return jnp.concatenate(
            [jax.lax.bitcast_convert_type(x, jnp.float32) for x in parts],
            axis=1,
        ).astype(ref.dtype)

    @pl.when(j < n_blocks)
    def _scores():
        s = jax.lax.dot_general(
            qm_ref[...], rows(k_ref), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (groups * n_kv, block_t)
        t = j * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s_ref[j] = jnp.where(t <= pos_ref[b], s, NEG_INF)

    @pl.when(j == n_blocks - 1)
    def _softmax():
        s = s_ref[...]  # (n_blocks, groups * n_kv, block_t)
        m = jnp.max(jnp.max(s, axis=2, keepdims=True), axis=0, keepdims=True)
        e = jnp.exp(s - m)
        total = jnp.sum(jnp.sum(e, axis=2, keepdims=True), axis=0,
                        keepdims=True)
        s_ref[...] = e / total
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= n_blocks)
    def _values():
        v = rows(v_ref)
        # the probabilities in V's dtype, as attn_decode's einsum takes them
        p = s_ref[j - n_blocks].astype(v.dtype)  # (groups * n_kv, block_t)
        acc_ref[...] += jnp.dot(p, v, preferred_element_type=jnp.float32)

    @pl.when(j == 2 * n_blocks - 1)
    def _out():
        mask = mask_ref[...]
        for g in range(groups):
            part = acc_ref[g * n_kv:(g + 1) * n_kv, :] * mask
            o_ref[g:g + 1, :] = jnp.sum(part, axis=0, keepdims=True).astype(
                o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("t_len", "block_t", "scale", "interpret"),
)
def lane_row_attention(
    buf_rows: jax.Array,  # (n_slots, rows, 128): the state buffer
    first: jax.Array,  # (2,) int32: first block of the K and V layer
    pos: jax.Array,  # (n_slots,) int32: keys 0..pos attend
    q: jax.Array,  # (n_slots, n_kv, groups, head_dim)
    *,
    t_len: int,
    block_t: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Attention of each slot's query ``q`` over ``t_len`` tokens of one
    K and one V layer in ``buf_rows``, a token's ``n_kv * head_dim``
    values in ``span`` rows of 128 lanes: the K layer's token ``t`` at
    row ``(first[0] * block_t + t) * span`` (V's likewise), scores
    scaled by ``scale``. Returns ``(n_slots, n_kv, groups, head_dim)``
    in ``q``'s dtype."""
    B, n_kv, groups, D = q.shape
    width = n_kv * D
    span = width // LANES
    n_blocks = t_len // block_t
    gk = groups * n_kv
    mask = jnp.asarray(_head_mask(n_kv, D))
    # the block-diagonal query: row g * n_kv + h holds query head (h, g)
    # in head h's lanes of a token row and zeros elsewhere
    qm = (q.transpose(0, 2, 1, 3).reshape(B, groups, 1, width)
          * mask.astype(q.dtype)).reshape(B, gk, width)

    def k_block(b, j, first, pos):
        return (b, first[0] + jnp.minimum(j, n_blocks - 1), 0)

    def v_block(b, j, first, pos):
        return (b, first[1] + jnp.maximum(j - n_blocks, 0), 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_blocks=n_blocks, block_t=block_t, span=span,
            groups=groups, n_kv=n_kv, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, 2 * n_blocks),
            in_specs=[
                pl.BlockSpec((None, gk, width), lambda b, j, f, p: (b, 0, 0)),
                pl.BlockSpec((n_kv, width), lambda b, j, f, p: (0, 0)),
                pl.BlockSpec((1, block_t * span, LANES), k_block),
                pl.BlockSpec((1, block_t * span, LANES), v_block),
            ],
            out_specs=pl.BlockSpec((None, groups, width),
                                   lambda b, j, f, p: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_blocks, gk, block_t), jnp.float32),
                pltpu.VMEM((gk, width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, groups, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(first, pos, qm, mask, buf_rows, buf_rows)
    return out.reshape(B, groups, n_kv, D).transpose(0, 2, 1, 3)
