"""AOT plan compiler: decode graph -> unified memory plan -> bundle.

The offline half of the compile→artifact→serve pipeline. For one
``(arch, n_slots, max_len, dtype)`` serving bucket this entrypoint:

1. traces the decode step to its liveness graph **at the shape level**
   (``jax.eval_shape`` parameter/cache pytrees — no weights are ever
   materialized, so compiling a plan for a 400B-parameter config costs
   megabytes, not terabytes) and derives the cross-step state records
   from the same shape-level cache pytree;
2. submits ONE :class:`~repro.core.unified.PlanSpec` to the unified
   facade (``repro.core.plan``): the activation half runs the paper's
   Offset Calculation portfolio — with ``--search`` also the memory-aware
   topological-order annealing and the MAFAT-style fusion search
   (``core/order_search`` / ``core/fusion_search``) against the cached
   planner — and the cross-step half gets the slot/KV shared-objects
   layout with concrete offsets;
3. gates the result through the static analyzer (default on; ``--no-lint``
   to skip): the O(n log n) soundness certifier
   (``repro.analysis.soundness``) re-derives liveness and proves the
   activation arena and state layout collision-free, and the bundle
   self-lint (``repro.analysis.bundle_lint``) checks fingerprint/shape
   coherence — error findings refuse the publish
   (:class:`repro.analysis.LintGateError`);
4. AOT-compiles the bucket's decode executables (decode step, slot
   reset, scan block — the exact programs the state backend jits,
   ``runtime/aot.py``) and serializes them into the bundle, so a served
   node performs **zero XLA compiles** on top of the zero traces / zero
   planner calls. Runs *behind* the lint gate (an unsound plan is
   refused before the expensive compiles), and the resulting executables
   are themselves audited (donation aliasing preserved through
   serialization, ``analysis/decode_lint.lint_executables``) before
   publish. ``--no-aot`` skips this step (smaller bundles, lazy-compile
   serving);
5. publishes a versioned, fingerprinted v4
   :class:`~repro.core.artifact.PlanBundle` carrying all of the above —
   plus, under ``--prefill-len``, the planned full-sequence *prefill*
   activation arena (the long-lifetime regime; the prefill shape joins
   the fingerprint and the bucket key) —
   into a content-addressed manifest directory that
   ``InferenceEngine(session=PlanSession.from_manifest(dir))`` /
   ``launch/serve.py --plan-bundle`` serve from without tracing,
   planning, laying anything out, or compiling anything.

``--all`` sweeps a whole fleet's bucket grid — every selected arch ×
``--slots-list`` × ``--max-lens`` (× ``--dtypes``) — into one manifest,
so ``serve.py`` bucket auto-selection (nearest compiled
``max_len >= requested``) can answer any admissible request with zero
traces and zero planner calls.

Usage:
    PYTHONPATH=src python -m repro.launch.compile --arch qwen3-0.6b \
        --search [--full] [--slots 4] [--max-len 128] [--out plan_artifacts]
    PYTHONPATH=src python -m repro.launch.compile --all \
        --slots-list 2 4 --max-lens 64 128 256 --out plan_artifacts
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shlex
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, ArchConfig, get_config, get_reduced
from repro.core.artifact import (
    BundleManifest,
    PlanBundle,
    bucket_key,
    graph_fingerprint,
    serve_fingerprint,
)
from repro.core.fusion_search import FusionSearchResult
from repro.core.graph import Graph
from repro.core.order_search import OrderSearchResult
from repro.core.plan_io import PlanCache
from repro.core.planner import MemoryPlan
from repro.core.unified import (
    PlanSpec,
    UnifiedPlan,
    detect_state_axes,
    plan as plan_unified,
    state_records_from_pytree,
)
from repro.launch.jax_cache import enable_compile_cache
from repro.models.api import Model, ShapeSpec
from repro.trace.jaxpr_liveness import trace_graph

DEFAULT_BUNDLE_DIR = "plan_artifacts"


@dataclasses.dataclass
class CompileResult:
    bundle: PlanBundle
    graph: Graph
    unified: UnifiedPlan
    greedy_plan: MemoryPlan
    order_result: OrderSearchResult | None
    fusion_result: FusionSearchResult | None
    wall_s: float

    @property
    def searched_total(self) -> int:
        return self.bundle.plan.total_size

    def summary(self) -> str:
        lines = [self.bundle.summary()]
        if self.order_result is not None and self.fusion_result is not None:
            evals = (
                self.order_result.evaluations + self.fusion_result.evaluations
            )
            hits = (
                self.order_result.cache_hits + self.fusion_result.cache_hits
            )
            lines.append(
                f"search: {evals} plan calls "
                f"({hits / max(evals, 1):.0%} cache hits), "
                f"order {self.order_result.plan.total_size / 2**20:.3f} MiB, "
                f"fused {self.fusion_result.plan.total_size / 2**20:.3f} MiB "
                f"({self.fusion_result.n_fused_groups} groups)"
            )
        lines.append(f"compile wall: {self.wall_s:.2f}s")
        return "\n".join(lines)


def _decode_specs(cfg: ArchConfig, *, n_slots: int, max_len: int):
    """(decode_fn, shape-level args) for the decode step — no weights are
    ever materialized, only avals."""
    if cfg.family == "audio":
        raise NotImplementedError("compile targets decoder-only archs")
    model = Model.for_config(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: model.init(key))
    caches = jax.eval_shape(lambda: model.init_cache(n_slots, max_len))
    tok0 = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
    pos0 = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    act0 = jax.ShapeDtypeStruct((n_slots,), jnp.bool_)

    def decode(p, t, c, pos, act):
        return model.decode_step(p, t, c, pos, active=act)

    return decode, (params, tok0, caches, pos0, act0)


def trace_decode_graph(
    cfg: ArchConfig, *, n_slots: int, max_len: int
) -> Graph:
    """Shape-level trace of the decode step — identical jaxpr (hence
    identical graph and plan) to what the engine would trace with real
    weights, since ``make_jaxpr`` only consumes avals."""
    decode, specs = _decode_specs(cfg, n_slots=n_slots, max_len=max_len)
    return trace_graph(decode, *specs, name=f"{cfg.name}-decode")


def _prefill_specs(cfg: ArchConfig, *, prefill_len: int):
    """(prefill_fn, shape-level args) for the full-sequence prefill of ONE
    request (batch 1 — the engine fills slots one request at a time).
    Works through ``Model.input_specs(kind="prefill")``, so modality
    frontends (prefix embeds, audio frames) are covered uniformly."""
    if prefill_len < 1:
        raise ValueError(f"prefill_len must be >= 1, got {prefill_len}")
    model = Model.for_config(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: model.init(key))
    batch = model.input_specs(
        ShapeSpec(f"prefill_{prefill_len}", prefill_len, 1, "prefill")
    )

    def prefill(p, b):
        return model.prefill(p, b)

    return prefill, (params, batch)


def trace_prefill_graph(cfg: ArchConfig, *, prefill_len: int) -> Graph:
    """Shape-level trace of the full-sequence prefill at ``prefill_len``
    tokens — the long-activation-lifetime regime the paper's strategies
    are strongest in. Same aval-only contract as the decode trace."""
    prefill, specs = _prefill_specs(cfg, prefill_len=prefill_len)
    return trace_graph(
        prefill, *specs, name=f"{cfg.name}-prefill{prefill_len}"
    )


def _measure_xla_temp(
    cfg: ArchConfig, *, n_slots: int, max_len: int
) -> int | None:
    """AOT-compile the decode step (shape-level) and read XLA's temp
    allocation, so bundle-served engines keep the planned-vs-XLA
    validation line without compiling anything at serving time."""
    decode, specs = _decode_specs(cfg, n_slots=n_slots, max_len=max_len)
    compiled = jax.jit(decode).lower(*specs).compile()
    return compiled.memory_analysis().temp_size_in_bytes or None


def compile_decode_plan(
    cfg: ArchConfig,
    *,
    n_slots: int,
    max_len: int,
    strategy: str = "auto",
    search: bool = False,
    search_iters: int = 300,
    fusion_rounds: int = 40,
    cache: PlanCache | None = None,
    measure_xla: bool = True,
    block_size: int = 1,
    greedy: bool = True,
    temperature: float = 1.0,
    top_k: int = 0,
    page_size: int | None = None,
    page_pool: int | None = None,
    prefill_len: int | None = None,
    lint: bool = True,
    aot: bool = True,
) -> CompileResult:
    """Trace → unified plan (both halves) → lint gate → AOT executables
    → bundle, in memory.

    ``block_size``/``greedy``/``temperature``/``top_k`` are the serving
    bucket's serve-loop configuration: they join the bundle fingerprint
    (``artifact.serve_fingerprint``), so a bundle compiled for the
    scan-block path self-invalidates against a default host-loop engine
    and vice versa. The planned layouts themselves do not change — the
    decode body traced for planning is the same graph the scan body
    iterates.

    ``prefill_len`` additionally traces and plans the full-sequence
    prefill activation arena at that many tokens; the bundle then carries
    both transient plans (the prefill arena aliases the decode arena —
    the phases never overlap in time) and ``prefill_len`` joins the
    fingerprint and the bucket key (``|pf{S}``)."""
    wall0 = time.perf_counter()
    serve_params = serve_fingerprint(
        block_size=block_size, greedy=greedy,
        temperature=temperature, top_k=top_k,
        page_size=page_size, page_pool=page_pool,
    )
    decode, specs = _decode_specs(cfg, n_slots=n_slots, max_len=max_len)
    graph = trace_graph(decode, *specs, name=f"{cfg.name}-decode")
    # the shape-level cache pytree (specs[2]) feeds the cross-step half
    state_records = state_records_from_pytree(specs[2], n_slots=n_slots)
    prefill_graph = (
        trace_prefill_graph(cfg, prefill_len=prefill_len)
        if prefill_len else None
    )

    unified = plan_unified(PlanSpec(
        graph=graph,
        state_records=state_records,
        cfg=cfg,
        n_slots=n_slots,
        max_len=max_len,
        serve_params=serve_params,
        strategy=strategy,
        search=search,
        search_iters=search_iters,
        fusion_rounds=fusion_rounds,
        cache=cache,
        page_size=page_size,
        page_pool=page_pool,
        prefill_graph=prefill_graph,
        prefill_len=prefill_len,
        state_token_axes=(
            detect_state_axes(
                Model.for_config(cfg).init_cache,
                n_slots=n_slots, max_len=max_len,
            )
            if page_size else None
        ),
    ))
    best_plan = unified.activation

    provenance = {
        "tool": "repro.launch.compile",
        **unified.provenance,
        # with AOT on, the measurement comes free from the pytree-decode
        # executable compile below (no separate throwaway compile)
        "xla_temp_bytes": (
            _measure_xla_temp(cfg, n_slots=n_slots, max_len=max_len)
            if measure_xla and not aot else None
        ),
    }
    if serve_params:
        provenance["serve_params"] = serve_params
    bundle = PlanBundle(
        fingerprint=unified.fingerprint,
        graph_fingerprint=graph_fingerprint(graph),
        arch=cfg.name,
        n_slots=n_slots,
        max_len=max_len,
        dtype=cfg.dtype,
        plan=best_plan,
        state_plan=unified.state,
        n_layers=cfg.n_layers,
        d_model=cfg.d_model,
        order=unified.order,
        fusion_groups=unified.fusion_groups,
        provenance=provenance,
        prefill_plan=unified.prefill,
        prefill_len=prefill_len or 0,
    )
    if lint:
        # the pre-publish gate: soundness certification (sweep-line,
        # independent of every planner) + bundle self-coherence. The O(n²)
        # oracle twin stays in core/validate for tests; this path must
        # scale to full-size graphs.
        from repro.analysis import LintGateError, bundle_lint, soundness
        from repro.analysis.findings import Report

        report = Report()
        report.extend(
            soundness.certify_bundle(bundle), checked="soundness"
        )
        report.extend(
            bundle_lint.lint_bundle(bundle, serve_params=serve_params),
            checked="bundle_lint",
        )
        if not report.ok():
            raise LintGateError(
                report,
                context=f"refusing to publish "
                f"{bucket_key(cfg, n_slots=n_slots, max_len=max_len, page_size=page_size, prefill_len=prefill_len)}",
            )
    if aot:
        # behind the lint gate on purpose: an unsound plan is refused
        # before the expensive XLA compiles. Each executable is the
        # residency impl the serving backend would jit, serialized for
        # zero-compile cold start (runtime/aot.py).
        from repro.runtime.aot import build_decode_executables

        pack, aot_xla_temp = build_decode_executables(
            cfg, unified.state,
            n_slots=n_slots, max_len=max_len,
            block_size=block_size, greedy=greedy,
            temperature=temperature, top_k=top_k,
        )
        if measure_xla and aot_xla_temp is not None:
            provenance = {**provenance, "xla_temp_bytes": aot_xla_temp}
        bundle = dataclasses.replace(
            bundle, executables=pack, provenance=provenance
        )
        if lint:
            # post-serialization audit: the executables must still carry
            # the donation aliasing (and stay free of host transfers) —
            # a serialization path that drops either is refused here
            from repro.analysis import LintGateError, decode_lint
            from repro.analysis.findings import Report

            report = Report().extend(
                decode_lint.lint_executables(bundle),
                checked="decode_lint:executables",
            )
            if not report.ok():
                raise LintGateError(
                    report,
                    context=f"refusing to publish AOT executables for "
                    f"{bucket_key(cfg, n_slots=n_slots, max_len=max_len, page_size=page_size, prefill_len=prefill_len)}",
                )
    outcome = unified.search
    return CompileResult(
        bundle=bundle,
        graph=graph,
        unified=unified,
        greedy_plan=outcome.greedy_plan if outcome is not None else best_plan,
        order_result=outcome.order if outcome is not None else None,
        fusion_result=outcome.fusion if outcome is not None else None,
        wall_s=time.perf_counter() - wall0,
    )


def compile_and_publish(
    cfg: ArchConfig,
    out_dir: str,
    *,
    n_slots: int,
    max_len: int,
    command: str | None = None,
    **kwargs,
) -> CompileResult:
    res = compile_decode_plan(cfg, n_slots=n_slots, max_len=max_len, **kwargs)
    BundleManifest(out_dir).publish(
        bucket_key(cfg, n_slots=n_slots, max_len=max_len,
                   page_size=kwargs.get("page_size"),
                   prefill_len=kwargs.get("prefill_len")),
        res.bundle,
        command=command,
    )
    return res


def sweep_buckets(
    archs: list[str],
    out_dir: str,
    *,
    full: bool = False,
    slots_list: list[int],
    max_lens: list[int],
    dtypes: list[str] | None = None,
    command: str | None = None,
    emit=print,
    explicit_archs: bool = False,
    dropped: list | None = None,
    **kwargs,
) -> list[CompileResult]:
    """The fleet sweep behind ``--all``: every (arch × slots × max_len ×
    dtype) bucket into ONE manifest. Plans are shared through one
    PlanCache across the sweep, so buckets differing only in max_len
    reuse each other's strategy runs when their record sets coincide.

    No silent caps: every arch or bucket the sweep drops is logged with
    its reason (and collected into ``dropped`` when the caller passes a
    list — ``(what, reason)`` pairs), and the sweep ends with a one-line
    drop summary. Audio (encoder-decoder) archs are skipped by default —
    the decode compile path targets decoder-only serving — but an
    explicit ``--archs`` listing (``explicit_archs=True``) opts them in:
    the sweep then *attempts* the compile so the drop reason is the real
    failure, not a guess, and audio archs start sweeping the moment the
    decode path learns to plan them."""
    cache = kwargs.pop("cache", None) or PlanCache()
    results: list[CompileResult] = []
    drops: list[tuple[str, str]] = dropped if dropped is not None else []
    for arch in archs:
        base = get_config(arch) if full else get_reduced(arch)
        if base.family == "audio" and not explicit_archs:
            reason = (
                "audio (encoder-decoder) arch — decode compile path is "
                "decoder-only; pass it via --archs to attempt anyway"
            )
            drops.append((arch, reason))
            emit(f"skip {arch}: {reason}")
            continue
        for dtype in dtypes or [base.dtype]:
            cfg = (
                base if dtype == base.dtype
                else dataclasses.replace(base, dtype=dtype)
            )
            for n_slots in slots_list:
                for max_len in max_lens:
                    key = bucket_key(
                        cfg, n_slots=n_slots, max_len=max_len,
                        page_size=kwargs.get("page_size"),
                        prefill_len=kwargs.get("prefill_len"),
                    )
                    try:
                        res = compile_and_publish(
                            cfg, out_dir, n_slots=n_slots, max_len=max_len,
                            command=command, cache=cache, **kwargs,
                        )
                    except NotImplementedError as e:
                        # un-plannable arch (today: audio opted in via an
                        # explicit --archs) — drop THE BUCKET, loudly
                        drops.append((key, str(e)))
                        emit(f"skip {key}: {e}")
                        continue
                    emit(
                        f"{key}"
                        f": {res.bundle.total_size / 2**20:.3f} MiB unified "
                        f"({res.wall_s:.2f}s)"
                    )
                    results.append(res)
    if drops:
        emit(
            f"dropped {len(drops)} arch(es)/bucket(s): "
            + ", ".join(what for what, _ in drops)
        )
    return results


def main() -> None:
    ap = argparse.ArgumentParser(
        description="compile decode-graph memory plans into serving bundles"
    )
    ap.add_argument("--arch", choices=ARCH_IDS,
                    help="one arch (or use --all)")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x slots x max-len) bucket into "
                         "one manifest; restrict archs with --archs")
    ap.add_argument("--archs", nargs="*", choices=ARCH_IDS, default=None,
                    help="arch subset for --all (default: every non-audio "
                         "arch)")
    ap.add_argument("--full", action="store_true",
                    help="compile the full config (default: reduced)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--slots-list", type=int, nargs="*", default=None,
                    help="slot counts for --all (default: --slots)")
    ap.add_argument("--max-lens", type=int, nargs="*", default=None,
                    help="max_len grid for --all (default: --max-len)")
    ap.add_argument("--dtypes", nargs="*", default=None,
                    help="dtype overrides for --all (default: each "
                         "config's own dtype)")
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--search", action="store_true",
                    help="run the order/fusion search on the decode graph")
    ap.add_argument("--iters", type=int, default=300,
                    help="order-search annealing iterations")
    ap.add_argument("--fusion-rounds", type=int, default=40)
    ap.add_argument("--block-size", type=int, default=1,
                    help="serve-loop block size the bundle is compiled "
                         "for (joins the fingerprint; 1 = host loop)")
    ap.add_argument("--sample", action="store_true",
                    help="compile for temperature/top-k sampling instead "
                         "of greedy (joins the fingerprint)")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=None,
                    help="compile a PAGED bucket: carve slot state into "
                         "fixed pages of this many bytes (joins the "
                         "fingerprint and the bucket key)")
    ap.add_argument("--page-pool", type=int, default=None,
                    help="physical pool page count for --page-size "
                         "(default: n_slots x pages-per-slot)")
    ap.add_argument("--prefill-len", type=int, default=None,
                    help="ALSO trace + plan the full-sequence prefill "
                         "activation arena at this many tokens (joins the "
                         "fingerprint and the bucket key as |pf{S}); "
                         "default: decode-only bundle")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the pre-publish static-analysis gate "
                         "(soundness certifier + bundle self-lint)")
    ap.add_argument("--no-aot", action="store_true",
                    help="skip AOT-compiling + serializing the decode "
                         "executables (smaller bundles; served engines "
                         "lazy-compile at the first wave)")
    ap.add_argument("--out", default=DEFAULT_BUNDLE_DIR,
                    help="bundle manifest directory")
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable summary line")
    args = ap.parse_args()
    if bool(args.arch) == bool(args.all):
        ap.error("pass exactly one of --arch or --all")
    enable_compile_cache()

    command = shlex.join(sys.argv)
    if args.all:
        results = sweep_buckets(
            list(args.archs or ARCH_IDS), args.out,
            full=args.full,
            slots_list=args.slots_list or [args.slots],
            max_lens=args.max_lens or [args.max_len],
            dtypes=args.dtypes,
            strategy=args.strategy, search=args.search,
            search_iters=args.iters, fusion_rounds=args.fusion_rounds,
            block_size=args.block_size, greedy=not args.sample,
            temperature=args.temperature, top_k=args.top_k,
            page_size=args.page_size, page_pool=args.page_pool,
            prefill_len=args.prefill_len,
            lint=not args.no_lint, aot=not args.no_aot,
            command=command,
            explicit_archs=args.archs is not None,
            dropped=(dropped := []),
        )
        print(f"published {len(results)} bucket(s) to {args.out}/")
        if args.json:
            print(json.dumps({
                "buckets": len(results),
                "unified_total_bytes": [r.bundle.total_size for r in results],
                "dropped": dropped,
                "wall_s": round(sum(r.wall_s for r in results), 3),
            }))
        return

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    res = compile_and_publish(
        cfg, args.out,
        n_slots=args.slots, max_len=args.max_len,
        strategy=args.strategy, search=args.search,
        search_iters=args.iters, fusion_rounds=args.fusion_rounds,
        block_size=args.block_size, greedy=not args.sample,
        temperature=args.temperature, top_k=args.top_k,
        page_size=args.page_size, page_pool=args.page_pool,
        prefill_len=args.prefill_len,
        lint=not args.no_lint, aot=not args.no_aot,
        command=command,
    )
    print(res.summary())
    print(f"published to {args.out}/ "
          f"(bucket {bucket_key(cfg, n_slots=args.slots, max_len=args.max_len, page_size=args.page_size, prefill_len=args.prefill_len)})")
    if args.json:
        print(json.dumps({
            "arch": args.arch,
            "full": args.full,
            "n_slots": args.slots,
            "max_len": args.max_len,
            "page_size": args.page_size,
            "prefill_len": args.prefill_len,
            "prefill_total_bytes": (
                res.bundle.prefill_plan.total_size
                if res.bundle.prefill_plan else None
            ),
            "greedy_total_bytes": res.greedy_plan.total_size,
            "bundle_total_bytes": res.bundle.plan.total_size,
            "state_total_bytes": (
                res.bundle.state_plan.total_size
                if res.bundle.state_plan else None
            ),
            "unified_total_bytes": res.bundle.total_size,
            "searched": args.search,
            "wall_s": round(res.wall_s, 3),
        }))


if __name__ == "__main__":
    main()
