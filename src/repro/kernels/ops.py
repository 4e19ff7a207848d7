"""Jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU (``interpret=False``, the default).
Tests on the CPU pass ``interpret=True``, which runs the kernel body in
the Pallas interpreter.
"""

from __future__ import annotations

import jax

from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd_chunk import ssd_chunk

__all__ = ["flash_decode", "ssd_chunk", "flash_decode_auto"]


def flash_decode_auto(q, k_cache, v_cache, lengths, **kw):
    """Pick block_t so a K/V tile pair stays within ~4 MiB of VMEM."""
    D = q.shape[-1]
    budget = 4 * 2**20
    per_pos = 2 * D * k_cache.dtype.itemsize
    block_t = max(128, min(2048, budget // per_pos // 128 * 128))
    return flash_decode(q, k_cache, v_cache, lengths, block_t=block_t, **kw)
