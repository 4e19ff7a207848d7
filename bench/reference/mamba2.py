"""Plain float32 forward pass of Mamba2 (arXiv:2405.21060; mamba_ssm
``Mamba2`` with its defaults), over a whole sequence: a causal depthwise
convolution, then the selective state-space recurrence run one position
at a time — no chunked SSD, no cache, no kernels.

Per layer, on the pre-norm residual stream:

    z, xBC, dt = in_proj(rmsnorm(h))
    x, B, C    = silu(causal_conv1d(xBC) + conv_bias)
    dt         = softplus(dt + dt_bias);  A = -exp(A_log)
    state_t    = exp(dt_t A) state_{t-1} + dt_t x_t (outer) B_t
    y_t        = state_t . C_t + D x_t
    h          = h + out_proj(rmsnorm(y * silu(z)))

then the final RMSNorm and the tied head. Sizes come from the
configuration file's published keys (d_state, headdim, expand, ngroups,
d_conv are the layer's defaults). Weights come in the served program's
parameter tree: ``period[0]`` stacks every layer (``ln1`` the block
norm, ``mamba.in_proj/out_proj`` as (in, out) matrices, ``conv_w``
(d_conv, channels) with tap ``d_conv - 1`` on the current position,
``conv_b``, ``A_log``, ``D``, ``dt_bias``, ``norm`` the gated norm);
``embed`` is the embedding and tied head; ``ln_f`` the final norm. Norm
weights are stored as ``scale`` with weight ``1 + scale``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _norm(x, scale, eps):
    x = x.astype(F32)
    w = 1.0 + scale.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def forward(params, tokens, config, mm=matmul):
    """Final-norm hidden states (B, T, d) in float32 for ``tokens`` (B, T)."""
    d = config["d_model"]
    N = config["d_state"]
    P = config["headdim"]
    G = config["ngroups"]
    K = config["d_conv"]
    eps = config["norm_epsilon"]
    d_inner = config["expand"] * d
    H = d_inner // P
    B, T = tokens.shape
    h = params["embed"][tokens].astype(F32)

    def layer(h, p):
        m = p["mamba"]
        zxbcdt = mm(_norm(h, p["ln1"], eps), m["in_proj"])
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner: 2 * d_inner + 2 * G * N]
        dt = zxbcdt[..., 2 * d_inner + 2 * G * N:]
        w = m["conv_w"].astype(F32)
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(padded[:, i: i + T] * w[i] for i in range(K))
        xbc = jax.nn.silu(conv + m["conv_b"].astype(F32))
        x = xbc[..., :d_inner].reshape(B, T, H, P)
        Bm = jnp.repeat(xbc[..., d_inner: d_inner + G * N].reshape(B, T, G, N),
                        H // G, axis=2)
        Cm = jnp.repeat(xbc[..., d_inner + G * N:].reshape(B, T, G, N),
                        H // G, axis=2)
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))  # (B, T, H)
        A = -jnp.exp(m["A_log"].astype(F32))

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp  # (B,H,P) (B,H,N) (B,H,N) (B,H)
            state = (state * jnp.exp(dt_t * A)[..., None, None]
                     + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            y = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)
            return state, y

        time_major = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        _, ys = jax.lax.scan(
            step, jnp.zeros((B, H, P, N), F32),
            (time_major(x), time_major(Bm), time_major(Cm), time_major(dt)))
        y = jnp.moveaxis(ys, 0, 1) + m["D"].astype(F32)[:, None] * x
        y = y.reshape(B, T, d_inner) * jax.nn.silu(z)
        h = h + mm(_norm(y, m["norm"], eps), m["out_proj"])
        return h, None

    h, _ = jax.lax.scan(layer, h, params["period"][0])
    return _norm(h, params["ln_f"], eps)


def unembed(params):
    """The tied output head as (d, vocab)."""
    return params["embed"].T
