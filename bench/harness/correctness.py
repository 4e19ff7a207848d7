"""Decide ``correct``: compare the served tokens with the plain reference.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest, is run through the float32
reference once each (prompt and served tokens together, teacher forced).
At every served position the reference's logits give the gap by which
the served token's logit lies below the reference's best; the widest
gap over the sample is the number compared. Greedy serving of a sound
program picks the reference's best token or a near tie, so the gap stays
at the size of the program's rounding. A wrong token, a state that did
not advance, or a slot that read another's cache lands far below.

The control runs the same reference in float8 (e4m3, each matrix and
each activation row scaled to the format's range) and reads, at each
position, the reference gap of the token the float8 pass puts first.
It must read above the limit that the program reads below.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
CHUNK = 128  # positions per block of logits


@dataclasses.dataclass
class Sample:
    tokens: np.ndarray  # (rows, T) prompt + served tokens, zero padded
    targets: np.ndarray  # (rows, T) the served token each position predicts
    mask: np.ndarray  # (rows, T) positions that predicted a served token
    served: int  # tokens compared


def fp8_matmul(x, w):
    """x @ w with both operands rounded to float8 e4m3 (per-row scale
    for x, per-column scale for w), accumulated in float32."""

    def q(a, axis):
        a = a.astype(F32)
        s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / E4M3_MAX
        s = jnp.where(s > 0, s, 1.0)
        return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    return jnp.matmul(q(x, -1), q(w, -2), precision=HIGHEST)


def shape_for(mix: dict, max_len: int) -> tuple[int, int]:
    """(rows, positions) of the comparison batch, fixed per cell so the
    reference compiles once: the mix's longest request, rounded up, and
    as many rows as keep the batch near 8192 positions."""
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    T = min(-(-longest // CHUNK) * CHUNK, max_len)
    return max(1, min(8, 8192 // T)), T


def draw(served: list[tuple[np.ndarray, list[int]]], seed: int, rows: int,
         T: int) -> Sample:
    """A seeded sample of ``served`` (prompt, tokens) pairs with the
    longest always in it."""
    order = sorted(range(len(served)), key=lambda i: -len(served[i][1]))
    rest = order[1:]
    picked = [order[0]] + [rest[i] for i in np.random.default_rng(
        [int(seed), 11]).permutation(len(rest))[: rows - 1]]
    tokens = np.zeros((rows, T), np.int32)
    targets = np.zeros((rows, T), np.int32)
    mask = np.zeros((rows, T), bool)
    for r, i in enumerate(picked):
        prompt, out = served[i]
        seq = np.concatenate([prompt, np.asarray(out, np.int32)])[:T + 1]
        n = len(seq) - 1
        tokens[r, :n] = seq[:n]
        targets[r, :n] = seq[1:]
        mask[r, len(prompt) - 1: n] = True
    return Sample(tokens, targets, mask, int(mask.sum()))


@functools.lru_cache(maxsize=None)
def _compare_fn(forward, unembed, config_items, control: bool):
    config = dict(config_items)

    def compare(params, tokens, targets, mask):
        h = forward(params, tokens, config)
        w = unembed(params)
        hc = forward(params, tokens, config, fp8_matmul) if control else None
        T = tokens.shape[1]
        gaps, cgaps = [], []
        for c in range(0, T, CHUNK):
            logits = jnp.matmul(h[:, c: c + CHUNK], w.astype(F32),
                                precision=HIGHEST)
            best = logits.max(-1)
            tgt = jnp.take_along_axis(logits, targets[:, c: c + CHUNK, None],
                                      -1)[..., 0]
            gaps.append(best - tgt)
            if control:
                pick = fp8_matmul(hc[:, c: c + CHUNK], w).argmax(-1)
                got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
                cgaps.append(best - got)
        gap = jnp.where(mask, jnp.concatenate(gaps, 1), 0.0).max()
        cgap = (jnp.where(mask, jnp.concatenate(cgaps, 1), 0.0).max()
                if control else jnp.float32(jnp.nan))
        return gap, cgap

    return jax.jit(compare)


def widest_gaps(reference, config: dict, params, sample: Sample, *,
                control: bool = False) -> tuple[float, float]:
    """(program's widest gap, control's widest gap or nan)."""
    items = tuple(sorted((k, v) for k, v in config.items()
                         if isinstance(v, (int, float, str, bool))))
    fn = _compare_fn(reference.forward, reference.unembed, items, control)
    with jax.default_matmul_precision("highest"):
        gap, cgap = fn(params, jnp.asarray(sample.tokens),
                       jnp.asarray(sample.targets), jnp.asarray(sample.mask))
    return float(gap), float(cgap)
