"""GQA attention: chunked-causal prefill + single-token cached decode.

Design points (TPU-shaped):
* prefill uses query-chunked attention (``lax.map`` over q blocks) so the
  score matrix never materializes at (S, S) — flash-attention's memory
  behavior expressed at the XLA level; block size 512 aligns to the MXU.
* decode attends one new token against a fixed-capacity KV cache.
* sliding-window layers keep a RING-BUFFER cache of size ``window`` —
  this is what makes gemma3's long_500k decode O(window) in memory for
  local layers (the paper-style liveness argument applied to KV state).
* GQA: kv heads broadcast to q heads via reshape (G groups).
* optional qk-norm (Qwen3).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import state_view
from repro.models.layers import init_linear, init_rms, rms_norm, rope

Constrain = Callable[[jax.Array, str], jax.Array] | None
NEG_INF = -1e30


def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qk_norm: bool, dtype, d_out: int | None = None) -> dict:
    """Projections from width ``d_model``, back to ``d_out`` (default
    ``d_model``)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_linear(kq, d_model, n_heads * head_dim, dtype),
        "wk": init_linear(kk, d_model, n_kv * head_dim, dtype),
        "wv": init_linear(kv, d_model, n_kv * head_dim, dtype),
        "wo": init_linear(ko, n_heads * head_dim, d_out or d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = init_rms(head_dim)
        p["k_norm"] = init_rms(head_dim)
    return p


def _project_qkv(p, x, n_heads, n_kv, head_dim, positions, theta, eps):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, S, n_kv, head_dim)
    v = (x @ p["wv"]).reshape(B, S, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,S,H,D), k: (B,T,KV,D) -> scores (B,H,S,T) with GQA broadcast."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k)
    return s.reshape(B, KV * G, S, k.shape[1])


def _gqa_out(probs, v):
    """probs: (B,H,S,T), v: (B,T,KV,D) -> (B,S,H*D)."""
    B, H, S, T = probs.shape
    KV = v.shape[2]
    G = H // KV
    p = probs.reshape(B, KV, G, S, T)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return o.reshape(B, S, H * v.shape[-1])


def attn_prefill(
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int | None,
    eps: float = 1e-6,
    q_chunk: int = 512,
    constrain: Constrain = None,
    positions: jax.Array | None = None,
    scale: float | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Causal (optionally windowed) attention over the full sequence,
    scores scaled by ``scale`` (default ``head_dim ** -0.5``).
    Returns (out (B,S,H*D), (k_cache, v_cache))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim, positions, theta, eps)
    if constrain is not None:
        q = constrain(q, "heads")
        k = constrain(k, "kv_heads")
        v = constrain(v, "kv_heads")
    if scale is None:
        scale = 1.0 / np.sqrt(head_dim)

    # Unrolled causal K-slicing halves score traffic but lets XLA overlap
    # chunk buffers (peak-memory regression at 32k) — so unroll only for
    # moderate S; long sequences use the sequential masked map (§Perf log).
    causal_unroll = window is None and S <= 8192
    if causal_unroll:
        q_chunk = max(q_chunk, -(-S // 16))  # bound the unroll at 16 bodies
    C = min(q_chunk, S)
    n_chunks = -(-S // C)
    pad = n_chunks * C - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(B, n_chunks, C, n_heads, head_dim).transpose(1, 0, 2, 3, 4)

    def _attend(qi, ki, vi, qpos, kpos):
        """qi (B,C,H,D) vs ki/vi (B,Lk,KV,D) with position masks."""
        s = _gqa_scores(qi, ki) * scale  # (B,H,C,Lk)
        mask = kpos[:, None, :] <= qpos[..., None]  # (B,C,Lk)
        if window is not None:
            mask = mask & (kpos[:, None, :] > qpos[..., None] - window)
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        return _gqa_out(probs, vi)  # (B,C,H*D)

    if window is not None and S > C:
        # Sliding-window: each q chunk only needs the last `window`+C keys.
        # Static slice length + dynamic start keeps lax.map applicable —
        # 32k prefill with a 1k window touches Lk=1.5k keys per 512-chunk
        # instead of all 32k (§Perf: local-layer score traffic ÷ ~21).
        Lk = min(S, (-(-(window - 1) // C) + 1) * C)

        def one_chunk(args):
            qi, start = args
            qpos = (start + jnp.arange(C))[None, :]
            k_start = jnp.clip(start + C - Lk, 0, S - Lk)
            ki = jax.lax.dynamic_slice_in_dim(k, k_start, Lk, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, k_start, Lk, axis=1)
            kpos = (k_start + jnp.arange(Lk))[None, :]
            return _attend(qi, ki, vi, qpos, kpos)

        starts = jnp.arange(n_chunks) * C
        outs = jax.lax.map(one_chunk, (qc, starts))
        out = outs.transpose(1, 0, 2, 3)
    elif causal_unroll and S > C:
        # Causal: chunk i attends keys [0, (i+1)·C) — an unrolled loop with
        # static per-chunk key lengths halves score FLOPs+bytes vs masking
        # a full (C, S) tile (§Perf). Chunk count is bounded by q_chunk
        # sizing above (≤ 16 bodies).
        outs = []
        kT = jnp.arange(S)[None, :]
        for i in range(n_chunks):
            hi = min((i + 1) * C, S)
            qpos = (i * C + jnp.arange(C))[None, :]
            outs.append(
                _attend(qc[i], k[:, :hi], v[:, :hi], qpos, kT[:, :hi])
            )
        out = jnp.stack(outs, axis=1)  # (B, n_chunks, C, H*D)
    elif S > C:
        # long-S causal: sequential masked map (flat memory profile)
        def one_chunk(args):
            qi, start = args
            qpos = (start + jnp.arange(C))[None, :]
            return _attend(qi, k, v, qpos, jnp.arange(S)[None, :])

        starts = jnp.arange(n_chunks) * C
        outs = jax.lax.map(one_chunk, (qc, starts))
        out = outs.transpose(1, 0, 2, 3)
    else:
        qpos = jnp.arange(S)[None, :]
        out = _attend(q, k, v, qpos, qpos)[:, None]
    out = out.reshape(B, n_chunks * C, n_heads * head_dim)[:, :S]
    out = out @ p["wo"]
    if window is not None:
        # ring-buffer cache: last `window` keys/values, slot i holds
        # position (S - window + i) when S >= window (see decode)
        W = window
        if S >= W:
            k_c, v_c = k[:, S - W :], v[:, S - W :]
        else:
            k_c = jnp.pad(k, ((0, 0), (0, W - S), (0, 0), (0, 0)))
            v_c = jnp.pad(v, ((0, 0), (0, W - S), (0, 0), (0, 0)))
        # roll so that cache slot = position % W  (ring invariant)
        shift = jnp.asarray((S - W) % W if S >= W else 0)
        k_c = jnp.roll(k_c, shift=shift, axis=1)
        v_c = jnp.roll(v_c, shift=shift, axis=1)
        return out, (k_c, v_c)
    return out, (k, v)


def attn_decode(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    cache: tuple[jax.Array, jax.Array],  # (B, T, KV, D) x2; T = cap or window
    pos: jax.Array,  # int32 scalar OR (B,) — per-slot positions (0-based)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int | None,
    eps: float = 1e-6,
    constrain: Constrain = None,
    active: jax.Array | None = None,  # (B,) bool — continuous batching mask
    scale: float | None = None,  # default head_dim ** -0.5
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode; returns (out (B,1,D_model-in), new cache).

    ``pos`` may be a vector for continuous batching: every batch row
    advances at its own position (scatter into its own cache row).
    Rows with ``active == False`` leave their cache untouched. The cache
    leaves are arrays, or views of a state buffer
    (``models/state_view.py``), into which only the new rows are
    written; the new cache is then the same views.
    """
    B = x.shape[0]
    T = cache[0].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim, pos_b[:, None], theta, eps)
    slot_b = pos_b % T if window is not None else jnp.minimum(pos_b, T - 1)
    if active is not None:
        slot_b = jnp.where(active, slot_b, T)  # T is OOB -> dropped
    # scatter one row per batch element (O(1) cache-bytes touched, unlike a
    # one-hot masked rewrite of the full cache)
    new_cache = (state_view.write_rows(cache[0], slot_b, k[:, 0]),
                 state_view.write_rows(cache[1], slot_b, v[:, 0]))
    k_cache, v_cache = map(state_view.read, new_cache)
    if constrain is not None:
        k_cache = constrain(k_cache, "kv_heads")
        v_cache = constrain(v_cache, "kv_heads")
        if not isinstance(new_cache[0], state_view.LeafView):
            new_cache = (k_cache, v_cache)
    if scale is None:
        scale = 1.0 / np.sqrt(head_dim)
    s = _gqa_scores(q, k_cache) * scale  # (B,H,1,T)
    idx = jnp.arange(T)[None, None, None, :]
    pb = pos_b[:, None, None, None]
    if window is None:
        mask = idx <= pb
    else:
        # slot i holds position: the largest p <= pos with p % T == i
        slot_pos = pb - ((pb - idx) % T)
        mask = (slot_pos >= 0) & (slot_pos <= pb) & (slot_pos > pb - window)
    s = jnp.where(mask, s, NEG_INF)
    probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = _gqa_out(probs, v_cache)  # (B,1,H*D)
    return out @ p["wo"], new_cache


def attn_decode_kernel(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    cache: tuple[jax.Array, jax.Array],
    pos: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int | None,
    eps: float = 1e-6,
    constrain: Constrain = None,
    active: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """attn_decode with the Pallas flash_decode kernel as the attention
    core (single-pass K/V streaming; see kernels/flash_decode.py). Global
    attention only — ring-buffer window layers need per-slot position
    masks the kernel does not model. Tests on the CPU pass
    ``interpret=True``."""
    from repro.kernels.flash_decode import flash_decode

    if window is not None:
        return attn_decode(
            p, x, cache, pos, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
            theta=theta, window=window, eps=eps, constrain=constrain,
            active=active,
        )
    B = x.shape[0]
    k_cache, v_cache = cache
    T = k_cache.shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim, pos_b[:, None], theta, eps)
    slot_b = jnp.minimum(pos_b, T - 1)
    if active is not None:
        slot_b = jnp.where(active, slot_b, T)
    rows = jnp.arange(B)
    k_cache = k_cache.at[rows, slot_b].set(k[:, 0], mode="drop")
    v_cache = v_cache.at[rows, slot_b].set(v[:, 0], mode="drop")
    G = n_heads // n_kv
    q_k = q.reshape(B, 1, n_kv, G, head_dim)[:, 0].transpose(0, 1, 2, 3)
    lengths = jnp.minimum(pos_b + 1, T).astype(jnp.int32)
    o = flash_decode(q_k, k_cache, v_cache, lengths, interpret=interpret)
    out = o.reshape(B, 1, n_heads * head_dim)
    return out @ p["wo"], (k_cache, v_cache)


def cross_attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype) -> dict:
    return attn_init(key, d_model, n_heads, n_kv, head_dim, False, dtype)


def cross_attn(
    p: dict,
    x: jax.Array,  # (B, S, D) decoder side
    enc_kv: tuple[jax.Array, jax.Array],  # precomputed (B, T, KV, D) x2
    *,
    n_heads: int,
    head_dim: int,
    constrain: Constrain = None,
) -> jax.Array:
    B, S, _ = x.shape
    k, v = enc_kv
    q = (x @ p["wq"]).reshape(B, S, n_heads, head_dim)
    if constrain is not None:
        q = constrain(q, "heads")
    s = _gqa_scores(q, k) / np.sqrt(head_dim)
    probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    return _gqa_out(probs, v) @ p["wo"]


def encode_kv(p: dict, enc_out: jax.Array, n_kv: int, head_dim: int):
    """Project encoder output once into cross-attention K/V."""
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, T, n_kv, head_dim)
    v = (enc_out @ p["wv"]).reshape(B, T, n_kv, head_dim)
    return k, v
