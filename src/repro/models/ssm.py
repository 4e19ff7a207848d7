"""Mamba2 block via SSD (state-space duality, arXiv:2405.21060).

Forward (prefill/train) uses the chunked SSD algorithm:
  * within-chunk: quadratic attention-like term with decay mask
  * across-chunk: sequential state recurrence via ``lax.scan`` over chunks
Decode is the O(1) recurrent update on the (B, H, P, N) state.

Block layout follows Mamba2: in_proj -> [z | xBC | dt], causal depthwise
conv over xBC, SSD core, gated RMSNorm, out_proj. Decode carries
(conv_state (B, K-1, conv_dim), ssm_state (B, H, P, N)).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import state_view
from repro.models.layers import init_linear, init_rms, rms_norm

Constrain = Callable[[jax.Array, str], jax.Array] | None


def ssm_dims(d_model: int, expand: int, head_dim: int, ngroups: int, dstate: int):
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    conv_dim = d_inner + 2 * ngroups * dstate
    return d_inner, nheads, conv_dim


def mamba_init(key, d_model: int, *, expand: int, head_dim: int,
               ngroups: int, dstate: int, conv: int, dtype) -> dict:
    d_inner, nheads, conv_dim = ssm_dims(d_model, expand, head_dim, ngroups, dstate)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": init_linear(k1, d_model, 2 * d_inner + 2 * ngroups * dstate + nheads, dtype),
        "conv_w": (jax.random.normal(k2, (conv, conv_dim), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads).astype(jnp.float32)),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm": init_rms(d_inner),
        "out_proj": init_linear(k4, d_inner, d_model, dtype),
    }


def _split_proj(cfg_dims, zxbcdt):
    d_inner, nheads, _ = cfg_dims["d_inner"], cfg_dims["nheads"], None
    ngroups, dstate = cfg_dims["ngroups"], cfg_dims["dstate"]
    z, xBC, dt = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner + 2 * ngroups * dstate],
        axis=-1,
    )
    return z, xBC, dt


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, ngroups: int,
                eps: float) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over each of ``ngroups`` equal groups of
    channels (Mamba2's gated norm; one group normalises all of them)."""
    g = y * jax.nn.silu(z)
    if ngroups == 1:
        return rms_norm(g, scale, eps)
    shape = g.shape
    g = g.reshape(*shape[:-1], ngroups, shape[-1] // ngroups)
    return rms_norm(g, scale.reshape(ngroups, -1), eps).reshape(shape)


def _step_size(dt: jax.Array, dt_bias: jax.Array, dt_min: float) -> jax.Array:
    """softplus(dt + dt_bias) in float32, floored at ``dt_min`` where set."""
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    return jnp.maximum(dt, dt_min) if dt_min else dt


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. xBC: (B, S, C); w: (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC)
    for i in range(K):  # K is small (4); unrolled taps
        out = out + pad[:, i : i + xBC.shape[1]] * w[i]
    return jax.nn.silu(out + b)


def mamba_prefill(
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    expand: int,
    head_dim: int,
    ngroups: int,
    dstate: int,
    conv: int,
    chunk: int = 256,
    eps: float = 1e-6,
    dt_min: float = 0.0,
    constrain: Constrain = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (out (B,S,D), (conv_state, ssm_state))."""
    B, S, D = x.shape
    d_inner, nheads, conv_dim = ssm_dims(D, expand, head_dim, ngroups, dstate)
    dims = dict(d_inner=d_inner, nheads=nheads, ngroups=ngroups, dstate=dstate)
    zxbcdt = x @ p["in_proj"]
    z, xBC_raw, dt = _split_proj(dims, zxbcdt)
    xBC = _causal_conv(xBC_raw, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + ngroups * dstate], axis=-1)
    H, P, G, N = nheads, head_dim, ngroups, dstate
    xs = xs.reshape(B, S, H, P)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    if constrain is not None:
        xs = constrain(xs, "ssm_heads")
    dt = _step_size(dt, p["dt_bias"], dt_min)  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,) negative
    dA = dt * A  # (B,S,H)

    # ---- chunked SSD ----
    C_len = min(chunk, S)
    n_chunks = -(-S // C_len)
    pad = n_chunks * C_len - S
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
    L = C_len
    NC = n_chunks

    def rs(t, tail):  # (B, S', ...) -> (NC, B, L, ...)
        return t.reshape(B, NC, L, *tail).transpose(1, 0, 2, *range(3, 3 + len(tail)))

    xs_c, Bm_c, Cm_c = rs(xs, (H, P)), rs(Bm, (G, N)), rs(Cm, (G, N))
    dt_c, dA_c = rs(dt, (H,)), rs(dA, (H,))

    # broadcast groups to heads (G divides H)
    rep = H // G

    def scan_body(state, inp):
        # state: (B,H,P,N) carried across chunks
        xc, Bc, Cc, dtc, dAc = inp  # (B,L,H,P), (B,L,G,N), ..., (B,L,H)
        cum = jnp.cumsum(dAc, axis=1)  # (B,L,H)
        total = cum[:, -1]  # (B,H)
        Bh = jnp.repeat(Bc, rep, axis=2)  # (B,L,H,N)
        Ch = jnp.repeat(Cc, rep, axis=2)
        # within-chunk (attention-like) term
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Lq,Lk,H)
        causal = jnp.tril(jnp.ones((L, L), bool))
        # mask BEFORE exp: exp of the (large positive) upper triangle would
        # be inf and poison gradients through the where
        seg = jnp.where(causal[None, :, :, None], seg, -jnp.inf)
        decay = jnp.exp(seg)
        qk = jnp.einsum("blhn,bmhn->blmh", Ch, Bh)  # (B,Lq,Lk,H)
        W = qk * decay * dtc[:, None, :, :]  # weight on x_m
        y_intra = jnp.einsum("blmh,bmhp->blhp", W.astype(xc.dtype), xc)
        # contribution of the incoming state
        state_decay = jnp.exp(cum)  # (B,L,H)
        y_inter = jnp.einsum(
            "blhn,bhpn->blhp", (Ch * state_decay[..., None]).astype(xc.dtype), state
        )
        # update state for next chunk
        rem = jnp.exp(total[:, None, :] - cum)  # (B,L,H) decay from l to end
        dBx = jnp.einsum(
            "blhn,blhp->bhpn",
            (Bh * (rem * dtc)[..., None]).astype(xc.dtype),
            xc,
        )
        new_state = state * jnp.exp(total)[..., None, None].astype(state.dtype) + dBx
        return new_state, y_intra + y_inter

    state0 = jnp.zeros((B, H, P, N), x.dtype)
    final_state, ys = jax.lax.scan(
        scan_body, state0, (xs_c, Bm_c, Cm_c, dt_c, dA_c)
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, NC * L, H, P)[:, :S]
    y = y + xs[:, :S] * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, d_inner)
    y = _gated_norm(y, z, p["norm"], ngroups, eps)
    out = y @ p["out_proj"]
    conv_state = xBC_raw[:, max(S - (conv - 1), 0) :]
    if S < conv - 1:
        conv_state = jnp.pad(conv_state, ((0, 0), (conv - 1 - S, 0), (0, 0)))
    return out, (conv_state, final_state)


def mamba_decode(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    cache: tuple,  # conv_state (B,K-1,conv_dim), ssm (B,H,P,N): arrays or views
    *,
    expand: int,
    head_dim: int,
    ngroups: int,
    dstate: int,
    conv: int,
    eps: float = 1e-6,
    dt_min: float = 0.0,
    constrain: Constrain = None,
    active: jax.Array | None = None,  # (B,) bool — freeze inactive states
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    B, _, D = x.shape
    d_inner, nheads, conv_dim = ssm_dims(D, expand, head_dim, ngroups, dstate)
    dims = dict(d_inner=d_inner, nheads=nheads, ngroups=ngroups, dstate=dstate)
    conv_state, state = map(state_view.read, cache)
    zxbcdt = x @ p["in_proj"]  # (B,1,·)
    z, xBC_new, dt = _split_proj(dims, zxbcdt)
    window = jnp.concatenate([conv_state, xBC_new], axis=1)  # (B,K,conv_dim)
    w = p["conv_w"]  # (K, C)
    xBC = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"])[:, None]
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + ngroups * dstate], axis=-1)
    H, P, G, N = nheads, head_dim, ngroups, dstate
    xs = xs.reshape(B, H, P)
    Bm = jnp.repeat(Bm.reshape(B, G, N), H // G, axis=1)  # (B,H,N)
    Cm = jnp.repeat(Cm.reshape(B, G, N), H // G, axis=1)
    dt_ = _step_size(dt[:, 0], p["dt_bias"], dt_min)  # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt_ * A)  # (B,H)
    new_state = (
        state * decay[..., None, None].astype(state.dtype)
        + jnp.einsum("bhp,bhn->bhpn", (xs * dt_[..., None].astype(xs.dtype)), Bm)
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cm)
    y = y + xs * p["D"][None, :, None].astype(y.dtype)
    y = y.reshape(B, 1, d_inner)
    y = _gated_norm(y, z, p["norm"], ngroups, eps)
    out = y @ p["out_proj"]
    new_conv = window[:, 1:]
    if active is not None:
        new_state = jnp.where(active[:, None, None, None], new_state, state)
        new_conv = jnp.where(active[:, None, None], new_conv, conv_state)
    # a cache of state-buffer views stores the new state in place
    return out, (state_view.write(cache[0], new_conv),
                 state_view.write(cache[1], new_state))
