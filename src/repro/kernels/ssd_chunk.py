"""Pallas TPU kernel: one Mamba2 SSD chunk (within-chunk + state update).

Grid (B, H): each program owns one (batch, head) pair and computes the
full L×L decay-weighted attention-like term plus the inter-chunk state
contribution in VMEM. L is the SSD chunk length (≤256), P = head dim,
N = state dim — the (L,L) weight tile, (L,P) x tile and (P,N) state tile
all fit VMEM simultaneously (≈ (256² + 256·64 + 64·128)·4B ≈ 0.3 MiB +
double-buffering), MXU-aligned at 128 where it matters.

The wrapper hands the kernel head-major views, so every block's last two
dimensions are whole array dimensions (the (8, 128) block rule Mosaic
enforces on the TPU): x/B/C as (B, H, L, ·) tiles, and the per-step
scalars dt and dA both as rows (B, H, 2, L) and as columns (B, H, L, 2).
The in-chunk cumulative sums are masked reductions over the (L, L) tile
in both orientations, so no in-kernel transpose or scan is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    """``a`` · ``b`` contracting dimension ``contract[0]`` of ``a`` with
    ``contract[1]`` of ``b`` (2-D operands, no batch dimensions)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _kernel(x_ref, row_ref, col_ref, b_ref, c_ref, s_ref, y_ref, ns_ref):
    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)
    state = s_ref[0, 0].astype(jnp.float32)  # (P, N)
    rows = row_ref[0, 0]  # (2, L): dt, dA
    cols = col_ref[0, 0]  # (L, 2): dt, dA
    dt_row, dA_row = rows[0:1, :], rows[1:2, :]  # (1, L)
    dt_col, dA_col = cols[:, 0:1], cols[:, 1:2]  # (L, 1)

    L = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    # inclusive cumulative sums of dA, as a column and as a row
    cum_col = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(causal, 0.0, dA_col), axis=0, keepdims=True)
    cum_row = cum_row + dA_row  # strict-upper sum + the diagonal term
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # (1, 1)

    seg = jnp.where(causal, cum_col - cum_row, -jnp.inf)  # (Lq, Lk)
    decay = jnp.exp(seg)
    qk = _dot(Cm, Bm, (1, 1))  # (Lq, Lk)
    W = qk * decay * dt_row
    y_intra = _dot(W, x, (1, 0))  # (L, P)
    y_inter = _dot(Cm * jnp.exp(cum_col), state, (1, 1))  # (L, P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    rem = jnp.exp(total - cum_col) * dt_col  # (L, 1)
    dBx = _dot(x, Bm * rem, (0, 0))  # (P, N)
    ns_ref[0, 0] = (state * jnp.exp(total) + dBx).astype(ns_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(
    x: jax.Array,  # (B, L, H, P)
    dt: jax.Array,  # (B, L, H) fp32
    dA: jax.Array,  # (B, L, H) fp32
    Bm: jax.Array,  # (B, L, H, N)
    Cm: jax.Array,  # (B, L, H, N)
    state: jax.Array,  # (B, H, P, N)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    head_major = (0, 2, 1, 3)
    scalars = jnp.stack([dt, dA], axis=-1).astype(jnp.float32)  # (B,L,H,2)
    scalar_cols = scalars.transpose(head_major)  # (B, H, L, 2)
    scalar_rows = scalars.transpose(0, 2, 3, 1)  # (B, H, 2, L)
    y, ns = pl.pallas_call(
        _kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 2, L), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, 2), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), state.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(
        x.transpose(head_major), scalar_rows, scalar_cols,
        Bm.transpose(head_major), Cm.transpose(head_major), state,
    )
    return y.transpose(head_major), ns
