"""The control: the plain reference computed in float8 in the program's
place must come out not correct, where the program comes out correct,
on the same served requests. At a size a test run can hold, on the CPU,
with a fixed set of requests served to completion (so the sample does
not depend on the CPU's speed); on the chip at the cells' own sizes it
is read by ``bench/probe.py --control`` (PERF.md, section 6). A run with
the control in the program's place reports ``correct`` false through
the same decision as a benchmark run."""

import time

import jax
import pytest

import tiny
import run as bench
from harness import correctness, spec, traffic, weights


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
def test_float8_control_fails_where_the_program_passes(config):
    from repro.models.api import Model
    from repro.runtime.engine import InferenceEngine

    cell = tiny.cell(config, "decode-batch", "bfloat16")
    cfg = bench.program_config(cell.config)
    model = Model.for_config(cfg)
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), 4)
    dep = cell.config["deployment"]
    engine = InferenceEngine(cfg, params, n_slots=dep["n_slots"],
                             max_len=dep["max_len"])
    stream = traffic.generate(cell.mix, 4, vocab=cell.config["vocab_size"],
                              n_slots=dep["n_slots"], seconds=1,
                              max_len=dep["max_len"])
    planned = stream.stream[:16]
    for r in planned:
        engine.submit(r.prompt, max_new_tokens=r.max_new)
    done = sorted(engine.run_until_done(), key=lambda q: q.request_id)
    pairs = [(r.prompt, q.tokens) for r, q in zip(planned, done)]
    rows, T = correctness.shape_for(cell.mix, dep["max_len"])
    sample = correctness.draw(pairs, 4, rows, T)
    gap, control = correctness.widest_gaps(
        spec.reference_module(tiny.ROOT, cell.config), cell.config, params,
        sample, control=True)
    limit = cell.config["correct"]["max_logit_gap"]
    assert gap < limit < control


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
def test_control_run_reports_not_correct(config):
    cell = tiny.cell(config, "decode-batch", "bfloat16")
    info = {"platform": "cpu", "kind": "cpu", "count": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    served = bench.serve(cell, 2**31 + 9, 1.5, False, bench.CompileMeter(),
                         time.perf_counter(), jax.devices()[0])
    line = bench.result(served, 2**31 + 9, False, info, control=True)
    limit = cell.config["correct"]["max_logit_gap"]
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > limit
    assert line["detail"]["program_max_logit_gap"] < limit
