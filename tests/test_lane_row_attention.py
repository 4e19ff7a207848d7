"""Decode attention read where K and V lie in the state buffer.

``attention.attn_decode`` on :class:`~repro.models.state_view.LeafView`
leaves whose heads straddle 128-lane rows runs the lane-row kernel
(``kernels/lane_row_attention.py``, the Pallas interpreter on the CPU);
on array leaves it runs the einsum over ``(B, T, KV, D)``, which is the
oracle here. Both write the same K and V rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import counters
from repro.kernels import lane_row_attention as lra
from repro.models import attention as attn
from repro.models import state_view

THETA = 10_000.0


def _buffer(B, T, KV, D, k_col, v_col, slot_elems, key):
    buf = (jax.random.normal(key, (B * slot_elems,), jnp.float32) * 0.5
           ).astype(jnp.bfloat16)
    slots = {"k": state_view.LeafSlot(k_col, (T, KV, D)),
             "v": state_view.LeafSlot(v_col, (T, KV, D))}
    return state_view.SlotBuffer(buf, B, slot_elems, slots)


def _leaf(state, name):
    slot = state.slots[name]
    region = state.buf.reshape(state.n_slots, state.slot_elems)
    return region[:, slot.col:slot.col + slot.size].reshape(
        state.n_slots, *slot.shape)


def _decode_in_place(p, x, pos, state, kwargs):
    """attn_decode on views of ``state``'s buffer, in one jit: (output,
    the buffer after the step's writes)."""

    def step(buf, x, pos):
        st = state_view.SlotBuffer(buf, state.n_slots, state.slot_elems,
                                   state.slots)
        views = st.views(st.slots, 0)
        out, _ = attn.attn_decode(p, x, (views["k"], views["v"]), pos,
                                  **kwargs)
        return out, st.buf

    return jax.jit(step)(state.buf, x, pos)


# (n_heads, n_kv, head_dim, T, where the K and V leaves start in a
# slot's region in elements, tokens the kernel reads a grid step (None:
# the einsum path))
CASES = {
    # zamba2's shared block: 32 ungrouped heads of 224, 56 rows a token,
    # two blocks of 128 tokens a leaf
    "zamba2-shared": (32, 32, 224, 256, (0, 256 * 7168), 128),
    # grouped: 12 query heads over 4 KV heads of 192, 6 rows a token
    "gqa-192": (12, 4, 192, 64, (0, 64 * 768), 64),
    # grouped heads of 64, two to a row, which XLA reads in place: the
    # einsum on the view
    "gqa-64": (14, 2, 64, 64, (0, 64 * 128), None),
    # the K leaf starts 8 tokens into the region: blocks of 8 tokens
    "unaligned-block": (8, 8, 224, 64, (8 * 1792, 72 * 1792), 8),
    # the K leaf starts 3 rows into a token row: no block fits
    "unaligned-row": (8, 8, 224, 64, (3 * 128, 72 * 1792), None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_attn_decode_in_place_matches_einsum(case):
    H, KV, D, T, (k_col, v_col), block_t = CASES[case]
    B = 3
    slot_elems = v_col + (T + 4) * KV * D
    state = _buffer(B, T, KV, D, k_col, v_col, slot_elems,
                    jax.random.PRNGKey(0))
    if 128 % D:
        assert lra.block_tokens(T, KV * D // 128, (k_col // 128, v_col // 128),
                                2, H) == block_t
    d_model = 256
    p = attn.attn_init(jax.random.PRNGKey(1), d_model, H, KV, D, False,
                       jnp.bfloat16)
    x = (jax.random.normal(jax.random.PRNGKey(2), (B, 1, d_model))
         ).astype(jnp.bfloat16)
    # lengths 1 and T, one in between; the last slot is inactive
    pos = jnp.array([0, T - 1, T // 3], jnp.int32)
    active = jnp.array([True, True, False])
    kwargs = dict(n_heads=H, n_kv=KV, head_dim=D, theta=THETA, window=None,
                  active=active, scale=(D / 2) ** -0.5)

    arrays = (_leaf(state, "k"), _leaf(state, "v"))
    want, (k_want, v_want) = attn.attn_decode(p, x, arrays, pos, **kwargs)
    before = np.asarray(state.buf)
    with counters.capture("lane_row_attention") as cap:
        got, buf = _decode_in_place(p, x, pos, state, kwargs)
    assert cap.delta("lane_row_attention") == (block_t is not None)
    state.buf = buf
    np.testing.assert_array_equal(np.asarray(_leaf(state, "k")),
                                  np.asarray(k_want))
    np.testing.assert_array_equal(np.asarray(_leaf(state, "v")),
                                  np.asarray(v_want))
    # the inactive slot's region is as it was
    after = np.asarray(state.buf).reshape(B, slot_elems)
    np.testing.assert_array_equal(after[2], before.reshape(B, slot_elems)[2])
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if block_t is None:  # the same einsum on a view of the same values
        np.testing.assert_array_equal(got, want)
    # both round their output to bf16 (and the einsum its scores): one
    # bf16 rounding step of the largest output apart at most
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 ** -8 * np.abs(want).max())


@pytest.mark.parametrize("arch,overrides,slots,max_len,expected", [
    # the benchmark's deployments: zamba2's stage of 12 layers holds both
    # shared blocks (heads of 224); qwen3-0.6b at its published head_dim
    # of 128 fills whole lane tiles; mamba2 has no attention
    ("zamba2-7b", {"depth": 12}, 8, 4096, 2),
    ("qwen3-0.6b", {"head_dim": 128}, 8, 2048, 0),
    ("mamba2-2.7b", {}, 16, 2048, 0),
])
def test_decode_trace_counts_lane_row_layers(arch, overrides, slots, max_len,
                                             expected):
    """Tracing a full-width resident decode step (shapes only) counts one
    ``lane_row_attention`` per attention layer read in place."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.core.unified import plan_state, state_records_from_pytree
    from repro.models.api import Model
    from repro.runtime import residency

    model = Model.for_config(
        dataclasses.replace(get_config(arch), **overrides))
    caches = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    plan = plan_state(state_records_from_pytree(caches, n_slots=slots),
                      n_slots=slots, max_len=max_len)
    addressing = residency.StateResidency(plan, caches, n_slots=slots)
    args = (
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        addressing.aval,
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
        *addressing.arg_avals(),
    )
    with counters.capture("lane_row_attention") as cap:
        jax.jit(residency.decode_impl(model, addressing)).trace(*args)
    assert cap.delta("lane_row_attention") == expected
