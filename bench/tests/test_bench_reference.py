"""The plain float32 references against the served program on the CPU
at a small size: the logits the engine computes while it feeds a prompt
through its decode step and then decodes agree with the reference's
full forward pass over the same tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import spec, weights


def _engine_logits(cfg_file, prompts, max_new):
    """Serve ``prompts`` on the default backend and decode loop; return the
    weights, each request's tokens and the logits row of every step that
    advanced it, captured beneath the engine."""
    import run as bench
    from repro.models.api import Model
    from repro.runtime.engine import InferenceEngine

    cfg = bench.program_config(cfg_file)
    model = Model.for_config(cfg)
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), 2**35 + 9)
    dep = cfg_file["deployment"]
    engine = InferenceEngine(cfg, params, n_slots=dep["n_slots"],
                             max_len=dep["max_len"])
    rows = {}  # slot -> list of logits rows, in order
    step = engine._step_tokens

    def capture(tokens, pos, active):
        logits = step(tokens, pos, active)
        for s in np.flatnonzero(active):
            rows.setdefault(int(s), []).append(np.asarray(logits[s]))
        return logits

    engine._step_tokens = capture
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    out = {}
    while engine.unfinished_requests():
        for req in engine.step():
            out[req.request_id] = req
    return params, out, rows


@pytest.mark.parametrize("name", ["qwen3-0.6b", "mamba2-2.7b"])
def test_prompt_fed_logits_match_the_reference(name):
    cfg_file = tiny.config(name, "float32", n_slots=1, max_len=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, size=n).astype(np.int32) for n in (9, 5)]
    # one slot, two requests: the second reuses the slot after a retirement
    params, reqs, rows = _engine_logits(cfg_file, prompts, max_new=6)
    ref = spec.reference_module(tiny.ROOT, cfg_file)
    at = 0
    for rid, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, reqs[rid].tokens])
        with jax.default_matmul_precision("highest"):
            h = ref.forward(params, jnp.asarray(seq[None, :-1]), cfg_file)
            logits = np.asarray(h[0] @ ref.unembed(params).astype(jnp.float32))
        n = len(seq) - 1  # prompt feed steps, then one wave per token
        got = np.stack(rows[0][at: at + n])
        at += n
        np.testing.assert_allclose(got, logits, atol=2e-3, rtol=2e-3)
        # greedy tokens are the reference's argmax
        assert list(logits[len(prompt) - 1:].argmax(-1)) == reqs[rid].tokens
