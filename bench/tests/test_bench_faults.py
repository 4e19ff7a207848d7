"""A run with the timed path broken underneath comes out not correct.

Each case drives the whole of a run but the look for a chip — weights,
engine, warm-up, window, the comparison with the reference — on a tiny
cell on the CPU, with one fault planted in the program: a decode step
that returns its state unchanged, half of the batch left out of every
step, or a token altered where it is produced. The sound run passes."""

import time

import jax
import jax.numpy as jnp
import pytest

import tiny
import run as bench
from repro.runtime.engine import InferenceEngine
from repro.runtime.residency import ResidentState

INFO = {"platform": "cpu", "kind": "cpu", "count": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _stale_state(monkeypatch):
    def decode(self, params, tokens, pos, active):
        logits, _ = self._decode(params, tokens, jnp.copy(self.buf), pos, active)
        return logits

    monkeypatch.setattr(ResidentState, "decode", decode)


def _half_batch(monkeypatch):
    sound = ResidentState.decode

    def decode(self, params, tokens, pos, active):
        return sound(self, params, tokens, pos,
                     active.at[active.shape[0] // 2:].set(False))

    monkeypatch.setattr(ResidentState, "decode", decode)


def _altered_token(monkeypatch):
    sound = InferenceEngine._sample_token
    calls = [0]

    def sample(self, row):
        calls[0] += 1
        tok = sound(self, row)
        return (tok + 1) % len(row) if calls[0] % 5 == 0 else tok

    monkeypatch.setattr(InferenceEngine, "_sample_token", sample)


def _run(cell):
    served = bench.serve(cell, 2**32 + 5, 1.5, False, bench.CompileMeter(),
                         time.perf_counter(), jax.devices()[0])
    return bench.result(served, 2**32 + 5, False, INFO)


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
def test_sound_run_is_correct(config):
    line = _run(tiny.cell(config, "decode-batch", "bfloat16"))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("config,fault", [
    ("qwen3-0.6b", _stale_state),
    ("mamba2-2.7b", _stale_state),
    ("qwen3-0.6b", _half_batch),
    ("qwen3-0.6b", _altered_token),
])
def test_broken_timed_path_is_not_correct(config, fault, monkeypatch):
    fault(monkeypatch)
    line = _run(tiny.cell(config, "decode-batch", "bfloat16"))
    assert not line["correct"]
    gap = line["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
