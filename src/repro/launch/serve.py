"""Serving driver: batched-request inference with the planned engine.

End-to-end example (deliverable (b)): build a reduced model, start the
InferenceEngine — from a precompiled plan artifact when ``--plan-bundle``
points at a bundle file or manifest directory (``launch/compile.py``
output), otherwise planning at construction — submit a batch of requests,
and print cold-start time, throughput and the memory report.

A manifest directory gets **bucket auto-selection**: if the exact
``(arch, slots, max_len, dtype)`` bucket is not compiled, the engine
serves the nearest compiled ``max_len >= requested`` (exact slots/dtype)
— a fleet swept with ``compile.py --all`` answers any admissible request
with zero traces and zero planner calls. ``--exact-bucket`` turns the
selection off.

``--compile-first`` runs the AOT compiler into the bundle directory before
starting the engine (the one-command demo of compile→artifact→serve);
``--compare-cold-start`` additionally measures **time-to-first-token**
(fresh engine construction + one served token, so the baseline pays its
lazy decode-jit XLA compile and the bundle path exercises its AOT
executables) for both the bundle and the plan-at-construction engine,
printing the columns side by side along with the decode compiles each
one paid.

Serving-loop knobs: ``--block-size K`` serves K decode waves per host
sync (the lax.scan block path with on-device sampling + stop detection —
one host sync per block instead of one per wave); ``--sample`` switches
greedy argmax to temperature/top-k sampling (``--temperature``,
``--top-k``, ``--seed``); ``--eos-id`` retires a request when it emits
that token. Block size and sampling knobs join the decode fingerprint,
so ``--compile-first`` publishes a bundle that matches them.
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, get_config, get_reduced
from repro.core.shared_objects import from_page_log, from_slot_log
from repro.core.unified import PlanSession
from repro.launch.jax_cache import enable_compile_cache
from repro.models.api import Model
from repro.runtime.engine import InferenceEngine


def _time_to_first_token(cfg, params, args, session) -> tuple[float, int]:
    """Construct a fresh engine and serve one request to its first
    emitted token(s) — the process-start→first-token path, including any
    lazy decode-jit XLA compile the engine pays on its first wave.
    Returns ``(seconds, decode compiles paid)``. One full block on the
    scan path (tail blocks of length < K lazy-compile by design, which
    would misattribute a compile to the AOT column)."""
    from repro.runtime import residency

    prompt = (
        np.random.default_rng(1)
        .integers(0, cfg.vocab, size=args.prompt_len)
        .astype(np.int32)
    )
    c0 = residency.COMPILE_CALLS
    t0 = time.perf_counter()
    engine = InferenceEngine(
        cfg, params, n_slots=args.slots, max_len=args.max_len,
        session=session,
        greedy=not args.sample, sample_seed=args.seed,
        temperature=args.temperature, top_k=args.top_k,
        eos_id=args.eos_id, block_size=args.block_size,
        page_size=args.page_size, page_pool=args.page_pool,
    )
    engine.submit(prompt, max_new_tokens=max(args.block_size, 1))
    engine.run_until_done()
    return time.perf_counter() - t0, residency.COMPILE_CALLS - c0


def run(argv: list[str] | None = None) -> dict:
    """Parse args, serve, return a stats dict (tests call this directly)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--plan-bundle", default=None,
                    help="precompiled plan artifact: a bundle file or a "
                         "manifest directory from launch/compile.py")
    ap.add_argument("--exact-bucket", action="store_true",
                    help="disable nearest-bucket auto-selection (serve "
                         "only an exact (slots, max_len, dtype) match)")
    ap.add_argument("--compile-first", action="store_true",
                    help="run the AOT compiler into --plan-bundle (default "
                         "plan_artifacts/) before starting the engine")
    ap.add_argument("--compare-cold-start", action="store_true",
                    help="also time a plan-at-construction engine so the "
                         "artifact's cold-start win is printed side by side")
    ap.add_argument("--block-size", type=int, default=1,
                    help="decode waves per host sync (1 = single-wave host "
                         "loop; K > 1 = lax.scan block decode with "
                         "on-device sampling and stop detection)")
    ap.add_argument("--sample", action="store_true",
                    help="temperature/top-k sampling instead of greedy "
                         "argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0,
                    help="sample seed (per-slot jax.random keys on the "
                         "block path)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a request when it emits this token")
    ap.add_argument("--page-size", type=int, default=None,
                    help="serve the PAGED state backend: per-slot page "
                         "tables over a pool of fixed pages of this many "
                         "bytes (joins the decode fingerprint)")
    ap.add_argument("--page-pool", type=int, default=None,
                    help="physical pool page count for --page-size "
                         "(default: slots x pages-per-slot)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if cfg.family == "audio":
        raise SystemExit("serve drives decoder-only archs; pick another --arch")

    bundle_dir = args.plan_bundle
    if args.compile_first:
        from repro.launch.compile import DEFAULT_BUNDLE_DIR, compile_and_publish

        bundle_dir = bundle_dir or DEFAULT_BUNDLE_DIR
        t0 = time.perf_counter()
        res = compile_and_publish(
            cfg, bundle_dir, n_slots=args.slots, max_len=args.max_len,
            command="launch/serve.py --compile-first",
            block_size=args.block_size, greedy=not args.sample,
            temperature=args.temperature, top_k=args.top_k,
            page_size=args.page_size, page_pool=args.page_pool,
        )
        print(f"compiled plan bundle in {time.perf_counter() - t0:.2f}s: "
              f"{res.bundle.summary()}")

    session = None
    if bundle_dir is not None:
        if Path(bundle_dir).is_dir():
            session = PlanSession.from_manifest(
                bundle_dir, nearest=not args.exact_bucket
            )
        else:
            session = PlanSession.from_bundle(bundle_dir)

    model = Model.for_config(cfg)
    print(f"initializing {cfg.name} ({cfg.n_layers}L d={cfg.d_model})...")
    params = model.init(jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    engine = InferenceEngine(
        cfg, params, n_slots=args.slots, max_len=args.max_len,
        session=session,
        greedy=not args.sample, sample_seed=args.seed,
        temperature=args.temperature, top_k=args.top_k,
        eos_id=args.eos_id, block_size=args.block_size,
        page_size=args.page_size, page_pool=args.page_pool,
    )
    cold_start_s = time.perf_counter() - t0
    report = engine.memory_report
    print(f"--- engine cold start: {cold_start_s:.3f}s "
          f"(plan source: {report.plan_source}) ---")
    if engine.max_len != args.max_len:
        print(f"--- bucket auto-selection: requested max_len={args.max_len} "
              f"-> serving the compiled len={engine.max_len} bucket ---")
    if engine.n_slots != args.slots:
        print(f"--- bucket auto-selection: requested slots={args.slots} "
              f"-> serving the compiled slots={engine.n_slots} pool ---")
    cold_start_noartifact_s = None
    ttft_s = ttft_noartifact_s = None
    ttft_compile_calls = ttft_noartifact_compile_calls = None
    if args.compare_cold_start and report.plan_source == "bundle":
        t0 = time.perf_counter()
        InferenceEngine(cfg, params, n_slots=args.slots, max_len=args.max_len)
        cold_start_noartifact_s = time.perf_counter() - t0
        print(f"--- cold start without the artifact: "
              f"{cold_start_noartifact_s:.3f}s "
              f"({cold_start_noartifact_s / max(cold_start_s, 1e-9):.1f}x "
              f"slower) ---")
        ttft_s, ttft_compile_calls = _time_to_first_token(
            cfg, params, args, session
        )
        ttft_noartifact_s, ttft_noartifact_compile_calls = (
            _time_to_first_token(cfg, params, args, None)
        )
        print(f"--- time to first token: {ttft_s:.3f}s from the bundle "
              f"({ttft_compile_calls} decode compiles) vs "
              f"{ttft_noartifact_s:.3f}s plan-at-construction "
              f"({ttft_noartifact_compile_calls} compiles, "
              f"{ttft_noartifact_s / max(ttft_s, 1e-9):.1f}x slower) ---")
    print("--- memory report (the paper's planner on the decode step) ---")
    print(report.summary())
    # planned-vs-live: with residency on, the engine's whole cross-step
    # state is ONE device buffer of exactly the planned size
    print(f"--- live device state: {report.state_live_bytes} B "
          f"(planned {report.state_planned_bytes} B, unified plan "
          f"{engine.unified_plan.total_size} B, residency "
          f"{'on' if report.state_residency else 'off'}) ---")

    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        engine.submit(
            rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
        )
    from repro.runtime import engine as engine_mod

    syncs0 = engine_mod.HOST_SYNCS
    t0 = time.perf_counter()
    done = engine.run_until_done()
    wall = time.perf_counter() - t0
    host_syncs = engine_mod.HOST_SYNCS - syncs0
    toks = sum(len(r.tokens) for r in done)
    print(f"--- served {len(done)} requests, {toks} tokens in {wall:.2f}s "
          f"({toks / wall:.1f} tok/s, {engine._wave} waves, "
          f"{host_syncs} host syncs"
          + (f" over {engine.n_blocks} scan blocks"
             if args.block_size > 1 else "")
          + ") ---")
    for r in done[:3]:
        print(f"req {r.request_id}: waves [{r.admitted_wave},{r.finished_wave}] "
              f"tokens {r.tokens[:8]}...")
    # slot-reuse audit: the engine's slot log IS a §4 shared-objects
    # assignment (slots = objects, requests = tensors); from_slot_log
    # raises if any two requests overlapped on one slot
    audit = from_slot_log(engine.slot_log, state_plan=report.state_plan)
    print(f"slot log (slot, admitted, finished, rid): {engine.slot_log}")
    print(f"slot audit: {len(audit.assignment)} requests over "
          f"{engine.n_slots} slots, no interval overlap")
    final_report = engine.memory_report
    pages_total = final_report.state_pages_total
    pages_live = final_report.state_pages_live
    pages_peak = None
    if engine.state.pages is not None:
        sp = report.state_plan
        pages_peak = engine.state.pages.peak
        # page-reuse audit, one level below the slot audit: pool pages
        # are the shared objects; raises if the runtime allocator ever
        # double-assigned a live page
        page_audit = from_page_log(engine.page_log, state_plan=sp)
        print(f"paged state: pool {pages_total} x {sp.page_size} B pages "
              f"(+1 null), peak live {pages_peak} "
              f"({pages_peak * sp.page_size} B = "
              f"{pages_peak / max(pages_total, 1):.0%} of the pool), "
              f"live now {pages_live}")
        print(f"page audit: {len(page_audit.assignment)} (request, page) "
              f"residencies over {pages_total} pool pages, no interval "
              f"overlap")
    return {
        "requests": len(done),
        "tokens": toks,
        "tokens_per_request": {r.request_id: list(r.tokens) for r in done},
        "prompts": {r.request_id: r.prompt.tolist() for r in done},
        "waves": engine._wave,
        "tokens_per_s": toks / wall if wall > 0 else None,
        "host_syncs": host_syncs,
        "blocks": engine.n_blocks,
        "block_size": args.block_size,
        "slot_log": list(engine.slot_log),
        "cold_start_s": cold_start_s,
        "cold_start_noartifact_s": cold_start_noartifact_s,
        "ttft_s": ttft_s,
        "ttft_compile_calls": ttft_compile_calls,
        "ttft_noartifact_s": ttft_noartifact_s,
        "ttft_noartifact_compile_calls": ttft_noartifact_compile_calls,
        "plan_source": report.plan_source,
        "bundle_warning": report.bundle_warning,
        "aot_executables": list(report.aot_executables),
        "aot_warning": report.aot_warning,
        "plan_total_bytes": report.activation_plan.total_size,
        "state_total_bytes": (
            report.state_plan.total_size if report.state_plan else None
        ),
        "unified_total_bytes": report.unified_total_bytes,
        "state_planned_bytes": report.state_planned_bytes,
        "state_live_bytes": final_report.state_live_bytes,
        "state_residency": report.state_residency,
        "page_size": final_report.state_page_size,
        "state_pages_total": pages_total,
        "state_pages_live": pages_live,
        "state_pages_live_peak": pages_peak,
        "page_log": list(engine.page_log),
        "requested_max_len": args.max_len,
        "effective_max_len": engine.max_len,
        "requested_slots": args.slots,
        "effective_slots": engine.n_slots,
    }


def main() -> None:
    run()


if __name__ == "__main__":
    main()
