"""AOT decode executables: compile at publish time, serve with ZERO
XLA compiles.

The v3 :class:`~repro.core.artifact.PlanBundle` closes the last
cold-start gap (ROADMAP item 1): after PR 3 a bundle-served engine did
zero traces and zero planner calls, but the decode jits still compiled
lazily at the first wave — 3–17 s of XLA compile per bucket vs a
0.01–0.3 s bundle load. This module compiles those jits offline and
ships the *executables* with the plan, the same ahead-of-time argument
the paper makes for memory ("the memory manager needs to run only once
before the first inference", §5) applied to compilation:

* :func:`build_decode_executables` lowers + compiles every program
  the state backend would jit, for the served addressing and the
  reference pytree — the module-level program factories in
  ``runtime/residency.py``, so the bundled executable IS the program
  the engine would have compiled — at the shape level (``jax.eval_shape``
  params, aval state buffer: no weights materialized), serializes each
  one through ``jax.experimental.serialize_executable``, and packs them
  into an :class:`~repro.core.artifact.ExecutablePack` keyed by
  ``jax.default_backend()`` + ``jax.__version__``;
* :func:`load_executables` is the serving side: refuse the whole pack
  with a one-line reason on a platform / jax-version / payload-integrity
  mismatch (serialized XLA executables are not portable across backends
  or jax releases) and let the engine fall back to lazy compile — a
  stale pack must never crash serving, and a *partial* pack is worse
  than none (the differential guarantees cover all-AOT or all-lazy).

Serialization is ``pickle`` of ``serialize_executable.serialize``'s
``(payload, in_tree, out_tree)`` triple — byte-deterministic for a fixed
program on the backends we CI (content addressing stays stable), and
donation metadata rides inside the executable (audited post-publish by
``analysis/decode_lint.lint_executables``).
"""

from __future__ import annotations

import contextlib
import hashlib
import pickle
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.artifact import (
    ExecutableEntry,
    ExecutablePack,
    PlanBundle,
    block_entry_name,
    executable_entry,
    expected_executable_entries,
)
from repro.core.unified import StatePlan
from repro.launch.jax_cache import persistent_cache_disabled
from repro.runtime.residency import (
    BLOCK_DONATE,
    DECODE_DONATE,
    RESET_DONATE,
    PytreeAddressing,
    block_impl,
    count_compile,
    decode_impl,
    reset_impl,
    state_addressing,
)
from repro.runtime.sampling import SamplingParams, TokenSampler


def serialize_compiled(compiled: Any) -> bytes:
    """One compiled jax executable -> opaque bundle payload bytes."""
    from jax.experimental import serialize_executable as se

    return pickle.dumps(se.serialize(compiled))


def deserialize_compiled(payload: bytes) -> Any:
    """Inverse of :func:`serialize_compiled`: a loaded, callable
    ``Compiled`` (positional args must match the lowering avals)."""
    from jax.experimental import serialize_executable as se

    return se.deserialize_and_load(*pickle.loads(payload))


# re-export: the canonical name list lives jax-free in core/artifact so
# analysis/bundle_lint can audit completeness without importing jax
expected_entries = expected_executable_entries


def build_decode_executables(
    cfg: Any,
    state_plan: StatePlan,
    *,
    n_slots: int,
    max_len: int,
    block_size: int = 1,
    greedy: bool = True,
    temperature: float = 1.0,
    top_k: int = 0,
) -> tuple[ExecutablePack, int | None]:
    """Compile + serialize every decode function for one serving bucket.

    Returns ``(pack, xla_temp_bytes)`` — the temp-allocation measurement
    comes free from the ``pytree_decode`` compile (the same plain
    cache-pytree program ``compile.py`` used to measure separately), so
    an AOT compile run costs no extra compiles over the measurement it
    replaces. Every ``.compile()`` here charges ``COMPILE_CALLS``: the
    whole point is to spend these offline so serving spends none."""
    from repro.models.api import Model

    model = Model.for_config(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    reference = PytreeAddressing(model, n_slots=n_slots, max_len=max_len)
    served = state_addressing(state_plan, reference.aval, n_slots=n_slots)
    sampler = TokenSampler(
        SamplingParams(greedy=greedy, temperature=temperature, top_k=top_k),
        max_len=max_len,
    )

    tok = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
    vec_i32 = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    vec_bool = jax.ShapeDtypeStruct((n_slots,), jnp.bool_)
    keys = jax.ShapeDtypeStruct((n_slots, 2), jnp.uint32)
    eos = jax.ShapeDtypeStruct((), jnp.int32)

    entries: dict[str, ExecutableEntry] = {}

    # XLA:CPU re-serializes an executable loaded from the persistent
    # compile cache without its function library, and the bundle then
    # fails when run (tests/test_aot.py, warm-cache case): on the CPU
    # these compiles skip the cache
    skip_cache = jax.default_backend() == "cpu"

    def _compile(name, fn, avals, donate):
        with (persistent_cache_disabled() if skip_cache
              else contextlib.nullcontext()):
            compiled = (
                jax.jit(fn, donate_argnums=donate).lower(*avals).compile()
            )
        count_compile()
        entries[name] = executable_entry(serialize_compiled(compiled))
        return compiled

    for addressing in (reference, served):
        prefix, state = addressing.prefix, addressing.aval
        extra = addressing.arg_avals()
        decode = _compile(
            f"{prefix}_decode", decode_impl(model, addressing),
            (params, tok, state, vec_i32, vec_bool, *extra),
            addressing.donated(DECODE_DONATE),
        )
        if addressing is reference:
            xla_temp = decode.memory_analysis().temp_size_in_bytes or None
        _compile(
            f"{prefix}_reset", reset_impl(model, addressing),
            (state, vec_bool, *extra), addressing.donated(RESET_DONATE),
        )
        if block_size > 1:
            _compile(
                block_entry_name(prefix, block_size),
                block_impl(model, addressing, sampler, block_size),
                (params, state, tok, vec_i32, vec_bool, vec_bool, vec_i32,
                 keys, eos, *extra),
                addressing.donated(BLOCK_DONATE),
            )

    pack = ExecutablePack(
        platform=jax.default_backend(),
        jax_version=jax.__version__,
        entries=entries,
    )
    return pack, xla_temp


def load_executables(
    bundle: PlanBundle,
) -> tuple[dict[str, Any], str | None]:
    """The serving-side load-or-refuse gate: ``(loaded entries, warning)``.

    All-or-nothing — any refusal (platform/jax-version key mismatch,
    payload integrity failure, deserialization error) drops the WHOLE
    pack and returns the one-line reason; the engine warns once and
    lazy-compiles, exactly as if the bundle were v2. ``({}, None)`` for
    bundles that simply carry no executables."""
    pack = bundle.executables
    if pack is None:
        return {}, None
    platform = jax.default_backend()
    if pack.platform != platform:
        return {}, (
            f"AOT executables were compiled for platform "
            f"{pack.platform!r} but this process runs {platform!r}; "
            f"falling back to lazy compile"
        )
    if pack.jax_version != jax.__version__:
        return {}, (
            f"AOT executables were compiled under jax {pack.jax_version} "
            f"but this process runs jax {jax.__version__}; falling back "
            f"to lazy compile"
        )
    loaded: dict[str, Any] = {}
    for name, entry in sorted(pack.entries.items()):
        if hashlib.sha256(entry.payload).hexdigest() != entry.sha256:
            return {}, (
                f"AOT executable {name!r} failed its payload integrity "
                f"check; falling back to lazy compile"
            )
        try:
            loaded[name] = deserialize_compiled(entry.payload)
        except Exception as e:
            return {}, (
                f"AOT executable {name!r} failed to deserialize "
                f"({type(e).__name__}: {e}); falling back to lazy compile"
            )
    return loaded, None


__all__ = [
    "build_decode_executables",
    "deserialize_compiled",
    "expected_entries",
    "load_executables",
    "serialize_compiled",
]
