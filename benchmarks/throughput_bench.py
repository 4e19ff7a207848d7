"""Decode-throughput benchmark: scan-block decode vs the host loop.

PR 5 made state bytes live == planned; this benchmark gives decode SPEED
the same committed-trajectory footprint (``BENCH_throughput.json``). For
each decoder arch (reduced configs — runs on CPU CI) it serves one
identical greedy workload twice:

* single-wave HOST loop (``block_size=1``): one decode dispatch + one
  host sync + numpy sampling per wave — the correctness oracle;
* SCAN-BLOCK loop (``block_size=K``): K waves per dispatch via
  ``lax.scan`` over the donated state buffer, sampling + stop detection
  on device, ONE host sync per block, and ``run_until_done``'s async
  pipelining (next block dispatched off the in-flight device carry
  before the previous block's results are fetched).

Measured per mode: tokens/s (wall of the real serving loop), p50/p99
per-token latency (a separate synchronous pass timing each sync unit —
``step()`` / ``step_block()`` — so percentiles are not polluted by the
async overlap), and host syncs per token (the ``engine.HOST_SYNCS``
counter).

Hard checks (regressions fail CI):
* greedy block decode is BYTE-IDENTICAL to the host loop: same tokens
  per request and same slot log;
* host syncs per scan block == 1 (the counter discipline);
* block tokens/s > host-loop tokens/s on every arch (the tentpole's
  measured speedup).

Usage:
    PYTHONPATH=src python benchmarks/throughput_bench.py --quick \
        --out BENCH_throughput.json
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import repro.runtime.engine as engine_mod
from repro.configs.base import get_reduced
from repro.launch.jax_cache import enable_compile_cache
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine

ARCHS = ("qwen3-0.6b", "granite-moe-3b-a800m", "mamba2-2.7b", "zamba2-7b")


def _make_engine(cfg, params, *, n_slots, max_len, block_size):
    return InferenceEngine(
        cfg, params, n_slots=n_slots, max_len=max_len, block_size=block_size
    )


def _submit_all(engine, prompts, max_new):
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)


def _warmup(engine, cfg, rng, *, max_new):
    """Compile every jit the measured run will hit (decode, reset, and —
    in block mode — the scan-block jit at the block lengths the workload
    produces), then drain."""
    engine.submit(rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                  max_new_tokens=max_new)
    engine.run_until_done()


def _timed_units(engine, prompts, max_new):
    """Synchronous pass for latency percentiles: wall-clock each sync
    unit (wave or block) and spread it over the waves it covered — one
    per-token latency sample per wave."""
    _submit_all(engine, prompts, max_new)
    samples = []
    step = engine.step if engine.block_size <= 1 else engine.step_block
    while engine._active or engine._queue:
        w0 = engine._wave
        t0 = time.perf_counter()
        step()
        wall = time.perf_counter() - t0
        waves = max(engine._wave - w0, 1)
        samples.extend([wall / waves] * waves)
    return samples


def bench_arch(arch: str, *, n_slots, max_len, requests, max_new,
               block_size, emit=print) -> dict:
    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=8).astype(np.int32)
               for _ in range(requests)]

    results = {}
    for mode, bs in (("host", 1), ("block", block_size)):
        # throughput: the real serving loop (async pipelining included)
        engine = _make_engine(cfg, params, n_slots=n_slots, max_len=max_len,
                              block_size=bs)
        _warmup(engine, cfg, rng, max_new=min(block_size, max_new))
        _submit_all(engine, prompts, max_new)
        syncs0, blocks0, waves0 = (
            engine_mod.HOST_SYNCS, engine.n_blocks, engine._wave,
        )
        t0 = time.perf_counter()
        done = engine.run_until_done()
        wall = time.perf_counter() - t0
        syncs = engine_mod.HOST_SYNCS - syncs0
        blocks = engine.n_blocks - blocks0
        waves = engine._wave - waves0
        toks = sum(len(r.tokens) for r in done)
        assert len(done) == requests, f"{arch}/{mode}: lost requests"
        if bs > 1:
            assert syncs == blocks, (
                f"{arch}: {syncs} host syncs over {blocks} scan blocks — "
                f"the block path must sync exactly once per block"
            )
        # latency percentiles: synchronous pass on a fresh engine
        lat_engine = _make_engine(cfg, params, n_slots=n_slots,
                                  max_len=max_len, block_size=bs)
        _warmup(lat_engine, cfg, rng, max_new=min(block_size, max_new))
        samples = _timed_units(lat_engine, prompts, max_new)
        results[mode] = {
            "engine": engine,
            "tokens": toks,
            "wall_s": wall,
            "tokens_per_s": toks / wall,
            "host_syncs": syncs,
            "syncs_per_token": syncs / toks,
            "waves": waves,
            "blocks": blocks,
            "done": {r.request_id: list(r.tokens) for r in done},
            "slot_log": [tuple(x) for x in engine.slot_log],
            "p50_ms": float(np.percentile(samples, 50) * 1e3),
            "p99_ms": float(np.percentile(samples, 99) * 1e3),
        }

    host, block = results["host"], results["block"]
    assert block["done"] == host["done"], (
        f"{arch}: greedy block decode tokens differ from the host loop"
    )
    assert block["slot_log"] == host["slot_log"], (
        f"{arch}: block decode slot log differs from the host loop"
    )
    speedup = block["tokens_per_s"] / host["tokens_per_s"]
    assert speedup > 1.0, (
        f"{arch}: scan-block decode ({block['tokens_per_s']:.1f} tok/s) "
        f"not faster than the host loop ({host['tokens_per_s']:.1f} tok/s)"
    )

    row = {
        "arch": arch,
        "tokens": host["tokens"],
        "host_tokens_per_s": round(host["tokens_per_s"], 2),
        "block_tokens_per_s": round(block["tokens_per_s"], 2),
        "speedup": round(speedup, 3),
        "host_waves": host["waves"],
        "block_syncs": block["host_syncs"],
        "blocks": block["blocks"],
        "host_syncs_per_token": round(host["syncs_per_token"], 4),
        "block_syncs_per_token": round(block["syncs_per_token"], 4),
        "host_p50_ms": round(host["p50_ms"], 3),
        "host_p99_ms": round(host["p99_ms"], 3),
        "block_p50_ms": round(block["p50_ms"], 3),
        "block_p99_ms": round(block["p99_ms"], 3),
        "greedy_identical": True,
    }
    emit(
        f"{arch}: host {host['tokens_per_s']:.1f} tok/s "
        f"({host['host_syncs']} syncs) -> block "
        f"{block['tokens_per_s']:.1f} tok/s ({block['host_syncs']} syncs, "
        f"{speedup:.2f}x); per-token p50 {host['p50_ms']:.2f} -> "
        f"{block['p50_ms']:.2f} ms, p99 {host['p99_ms']:.2f} -> "
        f"{block['p99_ms']:.2f} ms; greedy tokens + slot log identical"
    )
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--archs", nargs="*", default=list(ARCHS))
    ap.add_argument("--block-size", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()
    requests = 4 if args.quick else 8
    max_new = 16 if args.quick else 32
    n_slots, max_len = 2, 128

    rows = [
        bench_arch(arch, n_slots=n_slots, max_len=max_len,
                   requests=requests, max_new=max_new,
                   block_size=args.block_size)
        for arch in args.archs
    ]

    if args.out:
        doc = {
            "bench": "decode_throughput",
            "n_slots": n_slots,
            "max_len": max_len,
            "requests": requests,
            "max_new": max_new,
            "block_size": args.block_size,
            "rows": rows,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
