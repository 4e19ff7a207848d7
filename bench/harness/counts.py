"""Operations and bytes of the served work, computed from the published
sizes in a configuration file — the same count whatever implements it.

What belongs to one model family (which matrices a layer holds, what a
position adds to the state) lives in ``bench/counts/<family>.py``, found
by the configuration's ``reference`` key; its ``dims(config)`` returns a
``Dims``. The arithmetic here is the same for every family.

A token is processed at a position; ``context`` is the number of
positions its attention reads, itself included. ``head`` says whether
the token's logits are needed: an output token's are, a prompt token's
that only fills the cache are not (the program may compute them anyway;
that is its cost, not the model's).

Bytes are the least a step must move through HBM: every weight once per
step, the embedding rows it gathers, the state it reads (attention keys
and values up to each slot's fill, a recurrent state whole) and the
state it writes (one new row of keys and values; a recurrent state
whole). Matrices count at the deployment's element size, vectors at
four bytes (the program keeps norms and SSM scalars in float32).
"""

from __future__ import annotations

import dataclasses

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    """One model's sizes, as the family-independent counts need them."""

    d: int  # width of the residual stream and of an embedding row
    layers: int
    vocab: int  # rows of the embedding (padded where the model pads)
    item: int  # bytes per element of matrices and state
    layer_matrix_params: int  # matrix weights of one layer
    layer_vector_params: int  # norms, conv taps, SSM scalars of one layer
    # operations of one layer per token besides its matrix products: a
    # part fixed per token and a part per position its attention reads
    layer_flops_fixed: float
    layer_flops_per_position: float
    state_bytes_per_token: int  # attention state one position adds to a slot
    fixed_state_bytes: int  # recurrent state a slot holds whatever its fill


def item_bytes(config: dict) -> int:
    """Bytes per element of the deployment's matrices and state."""
    return _ITEM[config["deployment"]["dtype"]]


def layer_token_flops(m: Dims, context: int) -> float:
    """Operations of one layer for one token at ``context`` positions."""
    return (2.0 * m.layer_matrix_params + m.layer_flops_fixed
            + m.layer_flops_per_position * context)


def token_flops(m: Dims, context: int, head: bool) -> float:
    flops = m.layers * layer_token_flops(m, context)
    return flops + (2.0 * m.d * m.vocab if head else 0.0)


def body_weight_bytes(m: Dims) -> int:
    """Every layer's weights and the final norm (the head excluded)."""
    return m.layers * (m.layer_matrix_params * m.item
                       + m.layer_vector_params * 4) + m.d * 4


def head_bytes(m: Dims) -> int:
    """The tied embedding read whole by the output head."""
    return m.vocab * m.d * m.item


def live_state_bytes(m: Dims, context: int) -> int:
    """State a slot needs at ``context`` positions filled."""
    return context * m.state_bytes_per_token + m.fixed_state_bytes


def step_cost(m: Dims, slots: list[tuple[int, bool]]) -> tuple[float, float]:
    """(operations, bytes) of one step over ``slots`` — ``(context,
    head)`` per slot advanced."""
    flops = sum(token_flops(m, c, h) for c, h in slots)
    nbytes = float(body_weight_bytes(m))
    nbytes += head_bytes(m) if any(h for _, h in slots) else 0
    for c, _ in slots:
        nbytes += m.d * m.item  # the embedding row gathered
        nbytes += (c - 1) * m.state_bytes_per_token  # state read
        nbytes += m.state_bytes_per_token  # the new row written
        nbytes += 2 * m.fixed_state_bytes  # recurrent state read, written
    return flops, nbytes
