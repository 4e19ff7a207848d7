"""Production mesh + sharding rules (TP on `model`, FSDP on `data`).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod = v5e-256 as (data=16, model=16); multi-pod
adds a leading ``pod`` axis: (pod=2, data=16, model=16) = 512 chips.

Sharding policy (resolved per-architecture for divisibility):
* params: Megatron tensor-parallel on the `model` axis (FFN hidden,
  attention heads/head_dim, experts, vocab) + FSDP on the `data` axis for
  the complementary dimension. Non-divisible dims fall back to replication
  (never GSPMD padding, so the roofline numbers stay clean).
* activations: batch on (pod, data) when divisible, `model`-axis features
  via with_sharding_constraint tags emitted inside the models
  (the ``constrain(x, tag)`` hooks).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Mesh + per-arch resolved activation/parameter rules.

    ``seq_parallel`` (Megatron-LM sequence parallelism, §Perf iteration):
    shard the residual stream's sequence dim over `model` so norms,
    residual adds and the scan-carried remat activations are 1/TP-degree
    per device; XLA inserts the all-gather at matmul entry /
    reduce-scatter at exit.
    """

    mesh: Mesh
    cfg: ArchConfig
    seq_parallel: bool = False

    # ---- axis sizes
    @property
    def model_size(self) -> int:
        return self.mesh.shape["model"]

    @property
    def data_size(self) -> int:
        d = self.mesh.shape["data"]
        return d * self.mesh.shape.get("pod", 1)

    @property
    def batch_axes(self):
        return ("pod", "data") if "pod" in self.mesh.shape else ("data",)

    # ---- helpers
    def _axis_if(self, dim: int, axis, size: int):
        return axis if dim % size == 0 and dim >= size else None

    def batch_axis_for(self, b: int):
        """Shard batch over (pod, data) when divisible, else just data,
        else replicate (long_500k's batch=1)."""
        full = int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))
        if b % full == 0:
            return self.batch_axes
        if b % self.mesh.shape["data"] == 0:
            return ("data",)
        return None

    # ---- activation constraint hook (models call constrain(x, tag))
    def constrain(self, x: jax.Array, tag: str) -> jax.Array:
        spec = self.activation_spec(x, tag)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec)
        )

    def activation_spec(self, x, tag: str):
        cfg, ms = self.cfg, self.model_size
        B = x.shape[0]
        batch = self.batch_axis_for(B)
        if tag == "hidden":  # (B, S, D)
            if (
                self.seq_parallel
                and x.ndim == 3
                and x.shape[1] % ms == 0
                and x.shape[1] >= ms
            ):
                return P(batch, "model", None)
            return P(batch, None, None)
        if tag == "ffn":  # (B, S, F)
            return P(batch, None, self._axis_if(x.shape[-1], "model", ms))
        if tag == "heads":  # (B, S, H, hd)
            # NEVER shard head_dim: the score einsum contracts it, turning
            # every score tensor into a partial sum that must be
            # all-reduced (measured 2×8.2 TB/step on llama4 prefill —
            # §Perf). Non-divisible head counts replicate; K/V pick up the
            # sequence dim instead (context-parallel attention).
            h_ax = self._axis_if(x.shape[-2], "model", ms)
            return P(batch, None, h_ax, None)
        if tag == "kv_heads":  # (B, T, KV, hd)
            kv_ax = self._axis_if(x.shape[-2], "model", ms)
            if kv_ax is None:
                # context parallelism: shard the cache/sequence dim; the
                # softmax over the sharded axis costs only a tiny
                # max/sum all-reduce, and the PV contraction all-reduces
                # one (B,C,H·hd) tile instead of (B,H,C,T) scores.
                t_ax = self._axis_if(x.shape[1], "model", ms)
                return P(batch, t_ax, None, None)
            return P(batch, None, kv_ax, None)
        if tag == "ssm_heads":  # (B, S, H, P)
            h_ax = self._axis_if(x.shape[-2], "model", ms)
            return P(batch, None, h_ax, None)
        if tag == "experts":  # (B, G, E, C, D)
            e_ax = self._axis_if(x.shape[2], "model", ms)
            return P(batch, None, e_ax, None, None)
        if tag == "experts_ff":  # (B, G, E, C, F)
            e_ax = self._axis_if(x.shape[2], "model", ms)
            f_ax = self._axis_if(x.shape[-1], "model", ms) if e_ax is None else None
            return P(batch, None, e_ax, None, f_ax)
        if tag == "logits":  # (B, S, V) or (B, V)
            # vocab dims are huge and rarely divisible (seamless 256206,
            # internvl 151655): GSPMD's padded uneven sharding is far
            # cheaper than replicating a (B,S,V) fp32 tensor — measured
            # 145 GB/chip on seamless train without this.
            v_ax = "model" if x.shape[-1] >= ms else None
            if x.ndim == 3:
                return P(batch, None, v_ax)
            return P(batch, v_ax)
        return None

    # ---- parameter shardings
    def param_spec(self, path: str, x) -> P:
        """Rule-based param partitioning from the pytree path + shape."""
        ms, cfg = self.model_size, self.cfg
        fsdp = "data"  # FSDP axis for the complementary dim
        shape = x.shape
        nd = x.ndim
        # strip the stacked scan axis (period params have leading n_periods)
        lead = 1 if "period" in path and nd >= 1 else 0
        dims = shape[lead:]

        def fit(d, axis_size):
            return d % axis_size == 0 and d >= axis_size

        name = path.rsplit("/", 1)[-1] if "/" in path else path
        spec: list = [None] * nd

        def put(rel_idx, axis, size):
            d = dims[rel_idx]
            if fit(d, size) and axis not in spec:
                spec[lead + rel_idx] = axis

        if name == "embed":
            # jit ARGUMENT shardings must divide evenly, so non-divisible
            # vocabs (seamless 256206) keep the vocab dim replicated here;
            # the logits activation constraint (uneven sharding is legal
            # inside the program) still distributes the big (B,S,V) tensor.
            put(0, "model", ms)  # vocab
            put(1, fsdp, self.mesh.shape["data"])
            return P(*spec)
        if len(dims) == 0:
            return P(*spec)
        if name in ("wq", "wk", "wv", "w_in", "w_gate", "in_proj") and len(dims) == 2:
            put(1, "model", ms)  # output features (heads*hd / d_ff / inner)
            put(0, fsdp, self.mesh.shape["data"])
            return P(*spec)
        if name in ("wo", "w_out", "out_proj", "out") and len(dims) == 2:
            put(0, "model", ms)  # input features
            put(1, fsdp, self.mesh.shape["data"])
            return P(*spec)
        if len(dims) == 3:  # MoE expert stacks (E, d_in, d_out)
            if fit(dims[0], ms):
                put(0, "model", ms)
                put(1, fsdp, self.mesh.shape["data"])
            else:
                # experts not divisible (granite's 40): shard the ff dim.
                # (§Perf note: dropping the FSDP dim here was tried to kill
                # the per-layer grad all-reduces and REFUTED — the
                # collectives are the stacked-scan grad sync, which XLA
                # keeps inside the backward loop regardless; see
                # EXPERIMENTS.md §Perf pair-4 investigation.)
                ff_rel = 2 if name in ("w_in", "w_gate") else 1
                put(ff_rel, "model", ms)
                put(2 if ff_rel == 1 else 1, fsdp, self.mesh.shape["data"])
            return P(*spec)
        if name == "router" and len(dims) == 2:
            return P(*spec)
        if name == "conv_w" and len(dims) == 2:
            put(1, "model", ms)  # conv channels follow the inner dim
            return P(*spec)
        if len(dims) == 1:
            # biases / norms / per-head scalars: replicate (cheap)
            return P(*spec)
        if len(dims) == 2:
            put(1, "model", ms)
            put(0, fsdp, self.mesh.shape["data"])
            return P(*spec)
        return P(*spec)

    def param_shardings(self, params: Any):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)

        def path_str(p):
            return "/".join(
                str(getattr(k, "key", getattr(k, "idx", k))) for k in p
            )

        specs = [
            NamedSharding(self.mesh, self.param_spec(path_str(p), x))
            for p, x in flat
        ]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def cache_shardings(self, caches: Any):
        """KV/state caches: batch over (pod, data) + a trailing heads or
        feature dim over `model` when divisible.

        Batch axis location is structural: decoder caches are
        ``{"period": (leading n_periods axis ⇒ batch = axis 1),
        "remainder": (batch = axis 0)}``; enc-dec caches are
        ``{"self"/"cross": (L, B, …) ⇒ batch = axis 1}``.
        """
        ms = self.model_size

        def spec_for(batch_axis):
            def f(x):
                spec: list = [None] * x.ndim
                b_ax = self.batch_axis_for(x.shape[batch_axis])
                spec[batch_axis] = b_ax
                if b_ax is None and x.ndim > batch_axis + 2:
                    # batch=1 (long_500k): the data axis would idle — shard
                    # the cache sequence dim over it instead (ring-style
                    # decode; the scatter picks the owning shard).
                    t = batch_axis + 1
                    ds = self.mesh.shape["data"]
                    if x.shape[t] % ds == 0 and x.shape[t] >= 16 * ds:
                        spec[t] = "data"
                # shard a trailing heads/features dim on model
                for i in (x.ndim - 2, x.ndim - 1, x.ndim - 3):
                    if (
                        i > batch_axis
                        and spec[i] is None
                        and x.shape[i] % ms == 0
                        and x.shape[i] >= ms
                    ):
                        spec[i] = "model"
                        break
                return NamedSharding(self.mesh, P(*spec))

            return f

        if isinstance(caches, dict) and "period" in caches:
            return {
                "period": jax.tree_util.tree_map(spec_for(1), caches["period"]),
                "remainder": jax.tree_util.tree_map(
                    spec_for(0), caches["remainder"]
                ),
            }
        if isinstance(caches, dict) and "self" in caches:
            return jax.tree_util.tree_map(spec_for(1), caches)
        return jax.tree_util.tree_map(spec_for(0), caches)

    def batch_shardings(self, batch: Any):
        def spec_for(x):
            b_ax = self.batch_axis_for(x.shape[0])
            return NamedSharding(self.mesh, P(b_ax, *([None] * (x.ndim - 1))))

        return jax.tree_util.tree_map(spec_for, batch)

    def replicated(self, tree: Any):
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, P()), tree
        )
