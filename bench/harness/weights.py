"""Random weights from the seed, made on the device in one jitted call,
in the layout and the types the program serves them in.

The layout is the program's parameter tree (``jax.eval_shape`` of its
``init``: shapes only, no values). The values are the benchmark's own,
drawn leaf by leaf from the seed by the rules below, so the plain
reference and the program read the same weights and neither made them.

Rules, by the leaf's name:

* norm scales (``ln*``, ``norm``, ``q_norm``, ``k_norm``): the program
  multiplies by ``1 + scale``; drawn N(0, 0.1), so every norm's weight
  differs from 1 and a norm left out shows;
* ``embed``: N(0, 1) / sqrt(d_model) — the tied head then gives logits
  of order one;
* ``A_log``: log of U(1, 16), ``dt_bias``: softplus⁻¹ of a log-uniform
  step in [1e-3, 1e-1], ``D``: U(0.5, 1.5) (Mamba2's initialisation,
  with D spread so a missing skip shows);
* ``conv_w``: U(-1, 1) / sqrt(d_conv), ``conv_b``: U(-0.5, 0.5);
* every other leaf is a matrix ``(..., fan_in, fan_out)``:
  N(0, 1) / sqrt(fan_in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORMS = {"ln1", "ln2", "ln_f", "ln", "norm", "q_norm", "k_norm"}


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size: ``jax.random.key`` keeps only the
    low 32 bits of a larger seed, so the high bits are folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    return names[-1] if names else ""


def _draw(key, name: str, aval: jax.ShapeDtypeStruct):
    shape, dtype = aval.shape, aval.dtype
    if name in NORMS:
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name == "embed":
        return (jax.random.normal(key, shape, dtype)
                * np.asarray(1.0 / np.sqrt(shape[-1]), dtype))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "D":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5).astype(dtype)
    if name in ("conv_w", "conv_b"):
        lim = 1.0 / np.sqrt(shape[-2]) if name == "conv_w" else 0.5
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim).astype(dtype)
    return (jax.random.normal(key, shape, dtype)
            * np.asarray(1.0 / np.sqrt(shape[-2]), dtype))


def make_params(shapes, seed: int):
    """Weights for the parameter tree ``shapes`` (avals), on the default
    device, from one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = [_draw(jax.random.fold_in(key, i), _leaf_name(path), aval)
               for i, (path, aval) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
