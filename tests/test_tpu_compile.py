"""Compile the served path for a described TPU v5e, without the chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Interpret-mode
tests cannot see what it refuses: a Pallas block whose last two
dimensions break the (8, 128) rule, or a program that does not fit one
chip's 16 GiB. These cases compile, at real widths:

* both Pallas kernels (qwen3-0.6b decode attention, mamba2-2.7b SSD
  chunk), which must lower to a Mosaic ``tpu_custom_call``;
* the decode step of full-width qwen3-0.6b at 8 slots x 4096 tokens on
  each state backend — the cache pytree, the resident flat buffer and
  the paged pool (1 MiB pages) — each of which must fit one chip, and
  full-width mamba2-2.7b's at 16 slots on the resident buffer. The
  resident step updates the donated buffer in place: it aliases it, and
  its temporaries stay under a quarter of the state.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file. The persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30
SLOTS, MAX_LEN = 8, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    from repro.launch.jax_cache import persistent_cache_disabled

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    with persistent_cache_disabled():
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )


@pytest.fixture(scope="module")
def on_chip(topo):
    """Map a pytree of avals onto one described v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )


def test_flash_decode_lowers_to_mosaic(on_chip):
    from repro.kernels.flash_decode import flash_decode

    B, KV, G, D, T = SLOTS, 8, 2, 64, MAX_LEN
    cache = jax.ShapeDtypeStruct((B, T, KV, D), jnp.bfloat16)
    args = on_chip((
        jax.ShapeDtypeStruct((B, KV, G, D), jnp.bfloat16), cache, cache,
        jax.ShapeDtypeStruct((B,), jnp.int32),
    ))
    compiled = flash_decode.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_chunk_lowers_to_mosaic(on_chip):
    from repro.kernels.ssd_chunk import ssd_chunk

    B, L, H, P, N = 1, 256, 80, 64, 128
    per_step = jax.ShapeDtypeStruct((B, L, H), jnp.float32)
    proj = jax.ShapeDtypeStruct((B, L, H, N), jnp.bfloat16)
    args = on_chip((
        jax.ShapeDtypeStruct((B, L, H, P), jnp.bfloat16), per_step,
        per_step, proj, proj,
        jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
    ))
    compiled = ssd_chunk.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_program(backend: str, on_chip, arch: str = "qwen3-0.6b",
                    slots: int = SLOTS):
    """(jitted decode step, avals) of full-width ``arch`` on one chip —
    the same program factories and donation the serving backend jits."""
    from repro.configs.base import get_config
    from repro.core.unified import (
        detect_state_axes,
        plan_paged_state,
        plan_state,
        state_records_from_pytree,
    )
    from repro.models.api import Model
    from repro.runtime import paging, residency

    model = Model.for_config(get_config(arch))
    caches = jax.eval_shape(lambda: model.init_cache(slots, MAX_LEN))
    records = state_records_from_pytree(caches, n_slots=slots)
    head = on_chip((
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
    ))
    tail = on_chip((
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
    ))
    if backend == "pytree":
        addressing = residency.PytreeAddressing(
            model, n_slots=slots, max_len=MAX_LEN
        )
    elif backend == "resident":
        plan = plan_state(records, n_slots=slots, max_len=MAX_LEN)
        addressing = residency.StateResidency(plan, caches, n_slots=slots)
    else:
        plan = plan_paged_state(
            records, n_slots=SLOTS, max_len=MAX_LEN, page_size=1 << 20,
            axes=detect_state_axes(model.init_cache, n_slots=SLOTS,
                                   max_len=MAX_LEN),
        )
        addressing = paging.PagedStateResidency(plan, caches, n_slots=SLOTS)
    fn = jax.jit(residency.decode_impl(model, addressing),
                 donate_argnums=addressing.donated(residency.DECODE_DONATE))
    return fn, (*head, on_chip(addressing.aval), *tail,
                *on_chip(addressing.arg_avals()))


@pytest.mark.parametrize("backend,arch,slots", [
    pytest.param("pytree", "qwen3-0.6b", SLOTS, id="pytree"),
    pytest.param("resident", "qwen3-0.6b", SLOTS, id="resident"),
    pytest.param("paged", "qwen3-0.6b", SLOTS, id="paged"),
    pytest.param("resident", "mamba2-2.7b", 16, id="resident-mamba2-2.7b"),
])
def test_full_width_decode_step_fits_one_v5e(backend, arch, slots, on_chip):
    fn, args = _decode_program(backend, on_chip, arch, slots)
    ma = fn.lower(*args).compile().memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"{backend} decode on v5e: temp {ma.temp_size_in_bytes} B, "
          f"arguments {ma.argument_size_in_bytes} B, output "
          f"{ma.output_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B")
    assert live < V5E_HBM_BYTES, f"{backend}: {live} B > one chip"
    if backend != "pytree":
        # the donated state buffer is reused in place, not copied out
        assert ma.alias_size_in_bytes >= ma.output_size_in_bytes - 2**24
    if backend == "resident":
        # each layer reads its state where it lies and writes back only
        # what it changed: no whole-state temporary
        state = args[2]
        state_bytes = state.size * state.dtype.itemsize
        assert ma.temp_size_in_bytes < state_bytes / 4, (
            f"{arch}: temp {ma.temp_size_in_bytes} B against a "
            f"{state_bytes} B state")
