"""Token sampling for the serving loop — host-side and on-device.

Two halves, one contract:

* the HOST half (:func:`softmax` / :func:`host_probs`) backs the
  single-wave host loop's sampled (``greedy=False``) draws, made in
  numpy from the wave's logits fetched once. Probabilities are computed in
  float64 and explicitly renormalized — the float32 path handed
  ``Generator.choice(p=...)`` vectors whose sum drifted past numpy's
  tolerance and raised "probabilities do not sum to 1" on large vocabs;
* the DEVICE half (:class:`TokenSampler`) folds token selection into the
  decode jit for the scan-block path (``runtime/residency.decode_block``):
  greedy argmax or temperature/top-k draws via ``jax.random.categorical``
  with per-slot PRNG keys, plus the per-wave stop bookkeeping (EOS /
  budget / max_len) that lets a whole block run without host involvement.

Greedy selection is one definition for both loops: :func:`greedy_tokens`
is traced into the scan block's sampler, and the host loop dispatches it
once per wave (:data:`greedy_tokens_jit`) and fetches only the picks.

A slot's key advances only when the slot EMITS a token, so on-device
sampling depends only on the slot's emission index — the sampled
trajectory for a fixed seed is invariant to the scan block size, not just
reproducible run-to-run.

:class:`SamplingParams` is the canonical record of the knobs; the part of
it that shapes the compiled decode graph joins the decode fingerprint
(``core/artifact.serve_fingerprint``) so precompiled bundles stay
self-invalidating. The seed never joins: it is runtime data (a traced key
argument), not graph structure.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """The serving loop's sampling knobs.

    ``greedy=True`` ignores (and canonicalizes away) ``temperature`` and
    ``top_k`` — they do not shape the greedy graph. ``top_k=0`` means no
    top-k filtering.
    """

    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0

    def __post_init__(self):
        if not self.greedy and self.temperature <= 0.0:
            raise ValueError(
                f"sampling temperature must be > 0, got {self.temperature}"
            )
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")


def greedy_tokens(logits):
    """Greedy pick of each row of ``(n_slots, vocab)`` logits: the first
    maximal index over the vocabulary axis, as int32 — numpy's argmax tie
    rule. Pure jax, so the scan block traces it inside its jit."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# the host loop's one dispatch per wave; compiles once per logits shape
greedy_tokens_jit = jax.jit(greedy_tokens)


def softmax(x: np.ndarray) -> np.ndarray:
    """float64 softmax with explicit renormalization.

    ``Generator.choice(p=...)`` validates ``abs(p.sum() - 1) < atol`` in
    the dtype of ``p``; a float32 softmax over a big vocab rounds past
    that tolerance often enough to raise in real runs. Promote first,
    renormalize explicitly after."""
    x = np.asarray(x, np.float64)
    e = np.exp(x - x.max())
    p = e / e.sum()
    return p / p.sum()


def host_probs(
    row: np.ndarray, *, temperature: float = 1.0, top_k: int = 0
) -> np.ndarray:
    """The host loop's sampling distribution for one logit row —
    temperature scaling + optional top-k masking, then the float64
    :func:`softmax`."""
    x = np.asarray(row, np.float64)
    if temperature != 1.0:
        x = x / temperature
    if top_k and top_k < x.size:
        kth = np.partition(x, -top_k)[-top_k]
        x = np.where(x < kth, -np.inf, x)
    return softmax(x)


class TokenSampler:
    """On-device token selection + per-wave stop bookkeeping.

    One instance per engine, closed over by the scan-block jit (its
    knobs are static: they select the traced graph). All methods are
    pure jax — safe inside ``lax.scan``.
    """

    def __init__(self, params: SamplingParams, *, max_len: int):
        self.params = params
        self.max_len = int(max_len)

    @staticmethod
    def init_keys(seed: int, n_slots: int):
        """Per-slot PRNG keys, (n_slots, 2) uint32 — one independent
        stream per slot, derived from the engine's sample seed."""
        return jax.random.split(jax.random.PRNGKey(int(seed)), n_slots)

    def _draw(self, logits, subkeys):
        x = logits.astype(jnp.float32) / self.params.temperature
        if self.params.top_k and self.params.top_k < x.shape[-1]:
            kth = jax.lax.top_k(x, self.params.top_k)[0][:, -1][:, None]
            x = jnp.where(x < kth, -jnp.inf, x)
        return jax.vmap(jax.random.categorical)(subkeys, x).astype(jnp.int32)

    def advance(self, logits, keys, tokens, pos, step_active, done, budget,
                eos):
        """One wave of post-logits bookkeeping, entirely on device.

        Selects the next token for every emitting slot; frozen slots
        (``~step_active``) keep their token, position, budget and key —
        a slot's key advances only on emission, so sampled trajectories
        are invariant to how waves are grouped into blocks. Folds the
        stop conditions (EOS, exhausted budget, max_len) into ``done``.
        ``eos`` is a traced int32 scalar; callers with no EOS pass -1
        (never matches a vocab token)."""
        if self.params.greedy:
            nxt = greedy_tokens(logits)
        else:
            split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            sub, carried = split[:, 0], split[:, 1]
            nxt = self._draw(logits, sub)
            keys = jnp.where(step_active[:, None], carried, keys)
        nxt = jnp.where(step_active, nxt, tokens[:, 0])
        new_pos = pos + step_active.astype(pos.dtype)
        new_budget = budget - step_active.astype(budget.dtype)
        stopped = step_active & (
            (nxt == eos)
            | (new_budget <= 0)
            | (new_pos >= self.max_len - 1)
        )
        return keys, nxt[:, None], new_pos, done | stopped, new_budget
