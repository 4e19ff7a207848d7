"""The traffic generator: every seed sends the same sizes at the same
times with other token ids, every stretch of the stream holds close to
the mean, closed loops start near steady state, and seeds of any size
work."""

import json

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import traffic

MIXES = ["decode-batch", "chat", "chat-bursty"]


def _mix(name):
    return json.loads((tiny.BENCH / "traffic" / f"{name}.json").read_text())


def _gen(name, seed, seconds=50.0):
    return traffic.generate(_mix(name), seed, vocab=1000, n_slots=8,
                            seconds=seconds, max_len=2048)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_the_tokens_not_the_work(name):
    a, b = _gen(name, 1), _gen(name, 2**40 + 3)
    for x, y in [(a.initial, b.initial), (a.stream, b.stream)]:
        if not x:
            continue
        assert [(len(r.prompt), r.max_new, r.offset_s) for r in x] == [
            (len(r.prompt), r.max_new, r.offset_s) for r in y]
        assert any(not np.array_equal(r.prompt, s.prompt) for r, s in zip(x, y))
    again = _gen(name, 2**40 + 3)
    assert [r.prompt.tolist() for r in again.stream] == [
        r.prompt.tolist() for r in b.stream]


def test_every_stretch_holds_close_to_the_mean():
    mix = _mix("decode-batch")
    k = mix["strata"]
    p, o = traffic.lengths(mix, 4 * k)
    strata = traffic._lognormal_strata(mix["prompt_tokens"], k)
    assert sorted(p[:k]) == sorted(strata)  # one of each stratum per block
    # adjacent low/high pairs: every even prefix is near its share of the mean
    for n in range(2, 4 * k, 2):
        assert abs(p[:n].sum() - n * strata.mean()) <= 0.6 * strata.mean() * 2
    assert p.min() >= mix["prompt_tokens"]["min"]
    assert o.max() <= mix["output_tokens"]["max"]


def test_closed_loop_starts_near_steady_state():
    t = _gen("decode-batch", 7, seconds=10)
    assert t.closed and len(t.initial) == 8 and not t.warmup
    budgets = sorted(r.max_new for r in t.initial)
    median = _mix("decode-batch")["output_tokens"]["median"]
    assert budgets == [int(np.ceil(median * (i + 0.5) / 8)) for i in range(8)]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in t.stream)


@pytest.mark.parametrize("name", ["chat", "chat-bursty"])
def test_open_loop_is_sorted_and_inside_the_window(name):
    t = _gen(name, 2**33 + 1)
    offsets = [r.offset_s for r in t.stream]
    assert offsets == sorted(offsets) and offsets[-1] < 50
    assert len(t.warmup) == 8 and not t.initial


def test_mix_longer_than_the_deployment_is_refused():
    with pytest.raises(ValueError):
        traffic.generate(_mix("decode-batch"), 1, vocab=10, n_slots=8,
                         seconds=1, max_len=1024)
