"""Plain float32 forward pass of Qwen3 (dense, GQA, qk-norm, RoPE,
SwiGLU, tied embeddings), as the published Hugging Face ``Qwen3Model``
computes it, over a whole sequence with a causal mask — no cache, no
batching tricks, no kernels.

Sizes come from the configuration file's published keys. Weights come
in the served program's parameter tree, which the benchmark fills from
the seed; the mapping is:

* ``embed`` (vocab, d): token embedding, and the tied output head;
* ``period[0]`` holds every layer stacked on axis 0: ``ln1``/``ln2``
  input and post-attention RMSNorm, ``attn.wq/wk/wv/wo`` the q/k/v/o
  projections as (in, out) matrices, ``attn.q_norm/k_norm`` the per-head
  RMSNorms, ``mlp.w_gate/w_in/w_out`` the gate/up/down projections;
* ``ln_f``: the final RMSNorm.

Every RMSNorm weight is stored as ``scale`` with weight ``1 + scale``
(the program's convention); the reference applies ``x / rms(x) * w``
with that ``w``. ``mm`` is the matrix product, so the benchmark can run
the same pass in a lower precision as the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def matmul(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def _norm(x, scale, eps):
    x = x.astype(F32)
    w = 1.0 + scale.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half RoPE over the last axis of (B, T, heads, head_dim)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions[:, None].astype(F32) * jnp.asarray(inv, F32)[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params, tokens, config, mm=matmul):
    """Final-norm hidden states (B, T, d) in float32 for ``tokens`` (B, T)."""
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    hd = config["head_dim"]
    eps = config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    B, T = tokens.shape
    pos = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    h = params["embed"][tokens].astype(F32)

    def layer(h, p):
        a = p["attn"]
        x = _norm(h, p["ln1"], eps)
        q = mm(x, a["wq"]).reshape(B, T, H, hd)
        k = mm(x, a["wk"]).reshape(B, T, KV, hd)
        v = mm(x, a["wv"]).reshape(B, T, KV, hd)
        q = _rope(_norm(q, a["q_norm"], eps), pos, theta)
        k = _rope(_norm(k, a["k_norm"], eps), pos, theta)
        # query head i reads key/value head i // (H // KV)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v,
                       precision=jax.lax.Precision.HIGHEST)
        h = h + mm(o.reshape(B, T, H * hd), a["wo"])
        x = _norm(h, p["ln2"], eps)
        m = p["mlp"]
        h = h + mm(jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_in"]),
                   m["w_out"])
        return h, None

    h, _ = jax.lax.scan(layer, h, params["period"][0])
    return _norm(h, params["ln_f"], eps)


def unembed(params):
    """The tied output head as (d, vocab)."""
    return params["embed"].T
