"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

The serving hot-spot: one query token per sequence attends to a length-T
cache. HBM traffic is dominated by streaming K/V once; the kernel tiles
the cache into VMEM blocks of ``block_t`` positions and keeps an online
softmax (m, l, acc) in VMEM scratch — the scratch buffers are the
Shared-Objects view at the VMEM level: the same tiles are reused across
all T/block_t grid steps (cf. paper §4; the tile working set is the
positional maximum of the kernel's tensor usage records).

Layout: q (B, KV, G, D) — G = H/KV query heads per KV head; cache
(B, T, KV, D); lengths (B,) valid entries per row. Grid (B, KV, nT) with
the T axis sequential ('arbitrary') so scratch carries across tiles. The
wrapper views the cache head-major, (B, KV, T, D), so each K/V block is
a (block_t, D) tile in its last two dimensions — the (8, 128) block rule
Mosaic enforces on the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
             *, block_t: int, scale: float):
    b = pl.program_id(0)
    t_idx = pl.program_id(2)
    n_t = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (Tt, D)
    v = v_ref[0, 0].astype(jnp.float32)  # (Tt, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, Tt)
    length = lengths_ref[b]
    positions = t_idx * block_t + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    s = jnp.where(positions < length, s, NEG_INF)

    m_prev = m_ref[...]  # (G, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (G, Tt)
    correction = jnp.exp(m_prev - m_new)  # (G, 1)
    l_new = l_ref[...] * correction + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * correction + p @ v
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(t_idx == n_t - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def flash_decode(
    q: jax.Array,  # (B, KV, G, D)
    k_cache: jax.Array,  # (B, T, KV, D)
    v_cache: jax.Array,  # (B, T, KV, D)
    lengths: jax.Array,  # (B,) int32 — valid cache entries per row
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, KV, G, D = q.shape
    T = k_cache.shape[1]
    block_t = min(block_t, T)
    n_t = -(-T // block_t)
    k_cache = k_cache.transpose(0, 2, 1, 3)  # (B, KV, T, D)
    v_cache = v_cache.transpose(0, 2, 1, 3)
    if T % block_t:
        pad = n_t * block_t - T
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = 1.0 / (D ** 0.5)
    grid = (B, KV, n_t)
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps get the prefetched scalar ref as a trailing arg
                pl.BlockSpec((1, 1, G, D), lambda b, h, t, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_t, D), lambda b, h, t, lens: (b, h, t, 0)),
                pl.BlockSpec((1, 1, block_t, D), lambda b, h, t, lens: (b, h, t, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, t, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)
    return out
