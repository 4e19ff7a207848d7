"""Config-driven decoder stack: dense / MoE / SSM / hybrid / VLM prefix.

The layer program is ``period × n_periods + remainder`` (configs/base.py).
Scanned period params are stacked with a leading ``n_periods`` axis, so the
HLO contains ONE period body regardless of depth — nemotron's 96 layers
compile as a 96-iteration scan of a single block.

Three entry points per model:
  * ``forward``      — full-sequence logits (training fwd)
  * ``prefill``      — full-sequence logits + per-layer caches
  * ``decode_step``  — one token with caches (serve_step for decode shapes)

Caches are pytrees mirroring the period structure:
  attn layers   -> (k, v) with capacity ``cache_len`` (ring buffer of
                   ``window`` for sliding-window layers)
  mamba layers  -> (conv_state, ssm_state)
Zamba2 hybrid layers (``LayerSpec.shared_block``) run one of the shared
transformer blocks (``params["shared"]``, closed over, not scanned)
before their mixer and keep that invocation's own (k, v) under
``"shared"``; the invocation's adapter and ``linear`` are the layer's
own params.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import state_view
from repro.models.layers import (
    activation,
    init_linear,
    init_rms,
    mlp_apply,
    mlp_init,
    rms_norm,
)

Constrain = Callable[[jax.Array, str], jax.Array] | None


# --------------------------------------------------------------- init


def _layer_init(key, cfg: ArchConfig, spec: LayerSpec) -> dict:
    keys = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p: dict[str, Any] = {"ln1": init_rms(cfg.d_model), "ln2": init_rms(cfg.d_model)}
    if spec.mixer == "attn":
        p["attn"] = attn.attn_init(
            keys[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.qk_norm, dtype,
        )
    elif spec.mixer == "mamba":
        p["mamba"] = ssm_mod.mamba_init(
            keys[0], cfg.d_model, expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_groups,
            dstate=cfg.ssm_state, conv=cfg.ssm_conv, dtype=dtype,
        )
    if spec.ffn == "mlp":
        p["mlp"] = mlp_init(keys[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    elif spec.ffn == "moe":
        p["moe"] = moe_mod.moe_init(
            keys[1], cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.act,
            cfg.shared_expert, dtype,
        )
    if spec.shared_block is not None:
        # this invocation's own adapter on the block's gate/up projection
        # and its linear on the block's output
        ka, kb, kl = jax.random.split(keys[2], 3)
        p["adapter_in"] = init_linear(ka, cfg.d_model, cfg.adapter_rank, dtype)
        p["adapter_out"] = init_linear(kb, cfg.adapter_rank, 2 * cfg.d_ff, dtype)
        p["linear"] = init_linear(kl, cfg.d_model, cfg.d_model, dtype)
    return p


def _shared_block_init(key, cfg: ArchConfig) -> dict:
    """One Zamba2 shared block: attention from width 2·d_model
    (concat(h, emb0)) back to d_model, then a gated MLP at d_model."""
    dtype = jnp.dtype(cfg.dtype)
    d2 = 2 * cfg.d_model
    hd = d2 // cfg.shared_attn_heads
    k1, k2 = jax.random.split(key)
    return {
        "ln": init_rms(d2),
        "attn": attn.attn_init(
            k1, d2, cfg.shared_attn_heads, cfg.shared_attn_heads, hd, False,
            dtype, d_out=cfg.d_model,
        ),
        "ln2": init_rms(cfg.d_model),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def init_params(cfg: ArchConfig, key) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_per, k_rem, k_shared, k_out = jax.random.split(key, 5)
    emb_scale = 1.0 / np.sqrt(cfg.d_model)
    params: dict[str, Any] = {
        "embed": (
            jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), jnp.float32)
            * emb_scale
        ).astype(dtype),
        "ln_f": init_rms(cfg.d_model),
    }
    period, n_periods, remainder = cfg.stack
    # scanned period params: one pytree per period position, each stacked
    # over n_periods
    period_params = []
    pkeys = jax.random.split(k_per, max(len(period), 1))
    for i, spec in enumerate(period):
        stacked = jax.vmap(lambda k: _layer_init(k, cfg, spec))(
            jax.random.split(pkeys[i], n_periods)
        )
        period_params.append(stacked)
    params["period"] = tuple(period_params)
    rkeys = jax.random.split(k_rem, max(len(remainder), 1))
    params["remainder"] = tuple(
        _layer_init(rkeys[i], cfg, spec) for i, spec in enumerate(remainder)
    )
    if cfg.shared_blocks:
        params["shared"] = tuple(
            _shared_block_init(k, cfg)
            for k in jax.random.split(k_shared, cfg.shared_blocks)
        )
    return params


# --------------------------------------------------------------- blocks


def _shared_block(bp, lp, h, emb0, cfg, constrain, cache=None, pos=None,
                  active=None):
    """Zamba2's shared block ``bp`` at one invocation, whose adapter and
    ``linear`` are the layer params ``lp``; no residual inside the block.
    Returns (t, new kv): ``t`` joins the Mamba layer's input."""
    with jax.named_scope("zamba2.shared"):
        x = rms_norm(jnp.concatenate([h, emb0], axis=-1), bp["ln"], cfg.rms_eps)
        heads = cfg.shared_attn_heads
        hd = 2 * cfg.d_model // heads
        kwargs = dict(
            n_heads=heads, n_kv=heads, head_dim=hd, theta=cfg.rope_theta,
            window=None, eps=cfg.rms_eps, constrain=constrain,
            scale=(hd / 2) ** -0.5,
        )
        if cache is None:
            a, kv = attn.attn_prefill(bp["attn"], x, **kwargs)
        else:
            a, kv = attn.attn_decode(bp["attn"], x, cache, pos, active=active,
                                     **kwargs)
        u = rms_norm(a, bp["ln2"], cfg.rms_eps)
        m = bp["mlp"]
        lora = (u @ lp["adapter_in"]) @ lp["adapter_out"]
        gate = u @ m["w_gate"] + lora[..., : cfg.d_ff]
        up = u @ m["w_in"] + lora[..., cfg.d_ff :]
        y = activation(cfg.act)(gate) * up
        if constrain is not None:
            y = constrain(y, "ffn")
        return (y @ m["w_out"]) @ lp["linear"], kv


def _block(
    p: dict,
    spec: LayerSpec,
    cfg: ArchConfig,
    h: jax.Array,
    emb0: jax.Array,
    shared_p: tuple | None,
    constrain: Constrain,
    cache: Any = None,
    pos: Any = None,
    decode: bool = False,
    active: jax.Array | None = None,
) -> tuple[jax.Array, Any, jax.Array]:
    """Apply one layer. Returns (h, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    cache = cache or {}
    mixer_in = h  # the residual stays h: a shared block feeds the mixer only
    if spec.shared_block is not None:
        t, kv = _shared_block(
            shared_p[spec.shared_block], p, h, emb0, cfg, constrain,
            cache.get("shared") if decode else None,
            pos if decode else None,
            active if decode else None,
        )
        mixer_in = h + t
        new_cache["shared"] = kv
    if spec.mixer == "attn":
        x = rms_norm(mixer_in, p["ln1"], cfg.rms_eps)
        kwargs = dict(
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, theta=cfg.rope_theta,
            window=spec.window, eps=cfg.rms_eps, constrain=constrain,
        )
        if decode:
            a, kv = attn.attn_decode(p["attn"], x, cache["attn"], pos,
                                     active=active, **kwargs)
        else:
            a, kv = attn.attn_prefill(p["attn"], x, **kwargs)
        h = h + a
        new_cache["attn"] = kv
    elif spec.mixer == "mamba":
        x = rms_norm(mixer_in, p["ln1"], cfg.rms_eps)
        kwargs = dict(
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            ngroups=cfg.ssm_groups, dstate=cfg.ssm_state, conv=cfg.ssm_conv,
            eps=cfg.rms_eps, dt_min=cfg.ssm_dt_min, constrain=constrain,
        )
        if decode:
            m, st = ssm_mod.mamba_decode(p["mamba"], x, cache["mamba"],
                                         active=active, **kwargs)
        else:
            m, st = ssm_mod.mamba_prefill(p["mamba"], x, **kwargs)
        h = h + m
        new_cache["mamba"] = st
    if spec.ffn == "mlp":
        h = h + mlp_apply(p["mlp"], rms_norm(h, p["ln2"], cfg.rms_eps), cfg.act, constrain)
    elif spec.ffn == "moe":
        delta, aux = moe_mod.moe_apply(
            p["moe"], rms_norm(h, p["ln2"], cfg.rms_eps),
            n_experts=cfg.n_experts, top_k=cfg.top_k, act=cfg.act,
            capacity_factor=cfg.capacity_factor, constrain=constrain,
        )
        h = h + delta
    if constrain is not None:
        h = constrain(h, "hidden")
    return h, new_cache, aux


# --------------------------------------------------------------- stacks


def _empty_cache_for_spec(
    spec: LayerSpec, cfg: ArchConfig, batch: int, cache_len: int, dtype
) -> dict:
    c: dict[str, Any] = {}
    if spec.shared_block is not None:
        d2 = 2 * cfg.d_model
        hd = d2 // cfg.shared_attn_heads
        c["shared"] = (
            jnp.zeros((batch, cache_len, cfg.shared_attn_heads, hd), dtype),
            jnp.zeros((batch, cache_len, cfg.shared_attn_heads, hd), dtype),
        )
    if spec.mixer == "attn":
        T = min(spec.window, cache_len) if spec.window else cache_len
        hd = cfg.resolved_head_dim
        c["attn"] = (
            jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype),
            jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype),
        )
    elif spec.mixer == "mamba":
        d_inner, nheads, conv_dim = ssm_mod.ssm_dims(
            cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state,
        )
        c["mamba"] = (
            jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
            jnp.zeros((batch, nheads, cfg.ssm_head_dim, cfg.ssm_state), dtype),
        )
    return c


def init_cache(cfg: ArchConfig, batch: int, cache_len: int) -> dict:
    """Pytree of decode caches; scanned positions stacked over n_periods."""
    dtype = jnp.dtype(cfg.dtype)
    period, n_periods, remainder = cfg.stack
    return {
        "period": tuple(
            jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_periods, *x.shape)),
                _empty_cache_for_spec(spec, cfg, batch, cache_len, dtype),
            )
            for spec in period
        ),
        "remainder": tuple(
            _empty_cache_for_spec(spec, cfg, batch, cache_len, dtype)
            for spec in remainder
        ),
    }


def _run_stack(
    params: dict,
    cfg: ArchConfig,
    h: jax.Array,
    emb0: jax.Array,
    constrain: Constrain,
    caches: dict | None,
    pos: Any,
    decode: bool,
    remat: bool = False,
    active: jax.Array | None = None,
) -> tuple[jax.Array, dict | None, jax.Array]:
    shared_p = params.get("shared")
    period, n_periods, remainder = cfg.stack
    if isinstance(caches, state_view.SlotBuffer):
        return _run_stack_in_place(
            params, cfg, h, emb0, constrain, caches, pos, active
        )

    def period_body(carry, xs):
        h, aux = carry
        layer_params = xs[: len(period)]
        layer_caches = xs[len(period) :] if caches is not None else [None] * len(period)
        new_caches = []
        for spec, lp, lc in zip(period, layer_params, layer_caches):
            h, nc, a = _block(
                lp, spec, cfg, h, emb0, shared_p, constrain, lc, pos, decode,
                active,
            )
            new_caches.append(nc)
            aux = aux + a
        return (h, aux), tuple(new_caches)

    if remat:
        period_body = jax.checkpoint(period_body)

    aux0 = jnp.zeros((), jnp.float32)
    xs: tuple = tuple(params["period"])
    if caches is not None:
        xs = xs + tuple(caches["period"])
    if n_periods > 0 and len(period) > 0:
        (h, aux), new_period_caches = jax.lax.scan(period_body, (h, aux0), xs)
    else:
        new_period_caches = tuple()
        aux = aux0
    new_rem_caches = []
    for i, spec in enumerate(remainder):
        lc = caches["remainder"][i] if caches is not None else None
        h, nc, a = _block(
            params["remainder"][i], spec, cfg, h, emb0, shared_p, constrain,
            lc, pos, decode, active,
        )
        new_rem_caches.append(nc)
        aux = aux + a
    out_caches = None
    if caches is not None or not decode:
        out_caches = {"period": new_period_caches, "remainder": tuple(new_rem_caches)}
    return h, out_caches, aux


def _run_stack_in_place(
    params: dict,
    cfg: ArchConfig,
    h: jax.Array,
    emb0: jax.Array,
    constrain: Constrain,
    state: state_view.SlotBuffer,
    pos: Any,
    active: jax.Array | None,
) -> tuple[jax.Array, state_view.SlotBuffer, jax.Array]:
    """Decode over a :class:`~repro.models.state_view.SlotBuffer`: the
    buffer is the period loop's carry, and each layer reads its cache as
    views of it and writes back only what it changed. Same layers, same
    math as the cache-pytree path of :func:`_run_stack`."""
    shared_p = params.get("shared")
    period, n_periods, remainder = cfg.stack

    def block(lp, spec, h, slots, layer):
        h, _, _ = _block(
            lp, spec, cfg, h, emb0, shared_p, constrain,
            state.views(slots, layer), pos, True, active,
        )
        return h

    def period_body(carry, xs):
        h, state.buf = carry
        *layer_params, i = xs
        for spec, lp, slots in zip(period, layer_params,
                                   state.slots["period"]):
            h = block(lp, spec, h, slots, i)
        return (h, state.buf), None

    if n_periods > 0 and len(period) > 0:
        (h, state.buf), _ = jax.lax.scan(
            period_body, (h, state.buf),
            (*params["period"], jnp.arange(n_periods)),
        )
    for lp, spec, slots in zip(params["remainder"], remainder,
                               state.slots["remainder"]):
        h = block(lp, spec, h, slots, 0)
    return h, state, jnp.zeros((), jnp.float32)


def reset_slots(caches: dict, keep: jax.Array) -> dict:
    """Zero cache rows where ``keep[b]`` is False (slot recycling: stale
    SSM states / conv windows must not leak into the next request; stale
    attention entries are already hidden by position masks but are zeroed
    too for hygiene). Period caches carry batch on axis 1 (after the
    n_periods axis), remainder caches on axis 0. The zeros are exact
    (``+0``, as a fresh cache holds), not a product with 0, so every
    backend's reset leaves the same bytes."""

    def mask(leaf, axis):
        shape = [1] * leaf.ndim
        shape[axis] = leaf.shape[axis]
        return jnp.where(keep.reshape(shape), leaf, jnp.zeros((), leaf.dtype))

    return {
        "period": jax.tree_util.tree_map(lambda x: mask(x, 1), caches["period"]),
        "remainder": jax.tree_util.tree_map(
            lambda x: mask(x, 0), caches["remainder"]
        ),
    }


def grow_caches(cfg: ArchConfig, caches: dict, new_len: int) -> dict:
    """Pad attention caches (axis=1 of (…, B, T, KV, hd)) to ``new_len``
    so decode can continue past the prefill length. Ring-buffer (window)
    caches and mamba states keep their size."""

    def pad_kv(kv, keep: int | None):
        k, v = kv
        T = k.shape[-3]
        target = min(keep, new_len) if keep else new_len
        if T >= target:
            return (k, v)
        pad = [(0, 0)] * k.ndim
        pad[-3] = (0, target - T)
        return (jnp.pad(k, pad), jnp.pad(v, pad))

    def grow_spec(spec: LayerSpec, c: dict) -> dict:
        out = dict(c)
        if "attn" in c:
            out["attn"] = pad_kv(c["attn"], spec.window)
        if "shared" in c:
            out["shared"] = pad_kv(c["shared"], None)
        return out

    period, _, remainder = cfg.stack
    return {
        "period": tuple(
            grow_spec(spec, c) for spec, c in zip(period, caches["period"])
        ),
        "remainder": tuple(
            grow_spec(spec, c) for spec, c in zip(remainder, caches["remainder"])
        ),
    }


# --------------------------------------------------------------- API


def embed_tokens(params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def logits_from_hidden(params, cfg: ArchConfig, h: jax.Array, constrain: Constrain):
    h = rms_norm(h, params["ln_f"], cfg.rms_eps)
    logits = h @ params["embed"].T
    if constrain is not None:
        logits = constrain(logits, "logits")
    return logits


def forward(
    params: dict,
    cfg: ArchConfig,
    tokens: jax.Array,  # (B, S) int32
    prefix_embeds: jax.Array | None = None,  # (B, P, D) VLM stub output
    constrain: Constrain = None,
    remat: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence logits (B, S_total, vocab) + aux loss."""
    h = embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    if constrain is not None:
        h = constrain(h, "hidden")
    emb0 = h
    h, _, aux = _run_stack(
        params, cfg, h, emb0, constrain, None, None, decode=False, remat=remat
    )
    return logits_from_hidden(params, cfg, h, constrain), aux


def prefill(
    params: dict,
    cfg: ArchConfig,
    tokens: jax.Array,
    prefix_embeds: jax.Array | None = None,
    constrain: Constrain = None,
) -> tuple[jax.Array, dict]:
    """Returns (logits for the LAST position (B, vocab), caches)."""
    h = embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    emb0 = h
    h, caches, _ = _run_stack(
        params, cfg, h, emb0, constrain, None, None, decode=False
    )
    return logits_from_hidden(params, cfg, h[:, -1:], constrain)[:, 0], caches


def decode_step(
    params: dict,
    cfg: ArchConfig,
    token: jax.Array,  # (B, 1) int32
    caches: Any,  # cache pytree, or a state_view.SlotBuffer
    pos: jax.Array,  # int32 scalar or (B,) per-slot positions
    constrain: Constrain = None,
    active: jax.Array | None = None,  # (B,) continuous-batching mask
) -> tuple[jax.Array, Any]:
    """serve_step: ONE new token against the caches. Returns (logits
    (B, vocab), new caches); a SlotBuffer is updated in place and
    returned."""
    h = embed_tokens(params, cfg, token)
    # Zamba2's shared blocks concatenate the token's own embedding;
    # during decode that is the current token's.
    emb0 = h
    if constrain is not None:
        h = constrain(h, "hidden")
    h, new_caches, _ = _run_stack(
        params, cfg, h, emb0, constrain, caches, pos, decode=True,
        active=active,
    )
    return logits_from_hidden(params, cfg, h, constrain)[:, 0], new_caches
