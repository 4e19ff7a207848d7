"""The load generator's window on a fake engine and clock: a request
that falls due while a long engine call runs is still due in the
window, and a rate counts the calls that returned by the window's end."""

import numpy as np

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import client, measures, traffic
from harness.spec import load_module


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


class Req:
    def __init__(self, rid, max_new):
        self.request_id, self.max_new = rid, max_new
        self.admitted_wave, self.tokens = -1, []


class Engine:
    """Every call takes 2.5 s, admits the queue and gives each request
    one token."""

    block_size = 1

    def __init__(self, clock):
        self.clock, self.live, self.n = clock, [], 0

    def submit(self, prompt, max_new_tokens):
        self.live.append(Req(self.n, max_new_tokens))
        self.n += 1
        return self.n - 1

    def unfinished_requests(self):
        return list(self.live)

    def step(self):
        self.clock.t += 2.5
        for r in self.live:
            r.admitted_wave = 0
            r.tokens.append(1)
        done = [r for r in self.live if len(r.tokens) >= r.max_new]
        self.live = [r for r in self.live if r not in done]
        return done


def test_requests_due_during_the_last_call_count():
    clock = Clock()
    stream = [traffic.Request(i, np.ones(2, np.int32), 1, t)
              for i, t in enumerate([0.0, 1.0, 2.0, 2.9, 3.5])]
    c = client.Client(Engine(clock), traffic.Traffic(False, 4, [], stream, []),
                      spans=False, clock=clock)
    run = c.window(3.0)
    # calls end at 2.5 and 5.0; the request due at 2.9 fell due inside
    # the second call and is recorded at the close, the one at 3.5 is not
    assert [r.due for r in run.records] == [0.0, 1.0, 2.0, 2.9]
    assert run.records[-1].token_times == []
    ctx = measures.Context(run, None, {}, 0.0, 0, None)
    ttft = load_module(tiny.BENCH / "metrics" / "ttft_p50_ms.py", "t_ttft")
    # waits 2.5, 4.0, 3.0 and 2.1 (5.0 - 2.9, no token by the close)
    assert ttft.read(ctx) == np.median([2500.0, 4000.0, 3000.0, 2100.0])
    rate = load_module(tiny.BENCH / "metrics" / "tokens_per_s.py", "t_rate")
    # one token by the end of the window at 3 s; the second call's two
    # tokens came after it
    assert rate.read(ctx) == 1 / 3.0
