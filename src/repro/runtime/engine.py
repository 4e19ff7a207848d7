"""Inference engine: plan-once memory management + batched serving.

This is where the paper's contribution becomes a first-class framework
feature. At engine construction we:

1. obtain the :class:`~repro.core.unified.UnifiedPlan` for the serving
   bucket from the engine's :class:`~repro.core.unified.PlanSession` —
   ``PlanSession.from_manifest(dir)`` serves a precompiled v2
   :class:`~repro.core.artifact.PlanBundle` covering BOTH halves
   (activation offsets + cross-step state layout) with no jaxpr trace, no
   planner call, and no state-layout work; bucket auto-selection picks
   the nearest compiled ``max_len >= requested``. ``from_spec`` plans a
   :class:`~repro.core.unified.PlanSpec` on demand (pre-searched graphs,
   pinned strategies). Without a session — or when a bundle's fingerprint
   does not match — the engine traces the decode step
   (``trace/jaxpr_liveness``) and plans it (paper §5), recording a
   one-line warning in the report;
2. lay out the activation arena straight from the plan's offsets
   (``engine.activation_layout``) and MATERIALIZE the cross-step state
   from the plan: with state residency on (default;
   ``REPRO_STATE_RESIDENCY=off`` to disable) the per-slot KV caches and
   decode buffers live as views over ONE flat device buffer of exactly
   ``StatePlan.total_size`` bytes (``runtime/residency.py``),
   donate-threaded through the decode jit so XLA reuses the same
   allocation every wave — the planned layout is the live layout, not
   an accounting overlay;
3. lay out the CROSS-STEP state (per-slot KV caches + decode buffers) as
   a Shared-Objects instance where ``op index == decode wave`` — slots
   are the shared objects, requests are the tensors (paper §4 applied
   above the XLA level, where XLA cannot help); the engine's slot log is
   the runtime audit (``shared_objects.from_slot_log``);
4. run continuous batching: fixed ``n_slots``, admit from queue on free,
   step all active slots each wave, retire on EOS/max_len.

The decode step itself is jit-compiled once; the engine never reallocates
its buffers (the state buffer is a donated jit argument, so the decode
writes each wave's new state into the same physical allocation).

Two serving loops share that state:

* the single-wave HOST loop (``block_size=1``, the default): one decode
  dispatch + one host sync per wave, then one fetch for sampling —
  the greedy picks, taken on the device, or the wave's logits for numpy
  draws on the host. This is the correctness oracle;
* the SCAN-BLOCK loop (``block_size=K``): K decode waves per dispatch via
  ``lax.scan`` over the donated state buffer, with sampling (greedy
  argmax or temperature/top-k with per-slot ``jax.random`` keys) and
  stop detection (EOS / token budget / max_len, a per-slot ``done`` mask
  freezing finished slots mid-block) folded into the jit — ONE host sync
  per block (``HOST_SYNCS`` counts them, same discipline as the
  zero-trace/zero-plan counters). ``run_until_done`` additionally
  pipelines blocks: when nothing is queued, the next block is dispatched
  — chained on the in-flight block's device carry — BEFORE the previous
  block's results are fetched, so host admit/retire bookkeeping overlaps
  device compute. Greedy block decode is byte-identical to the host loop
  (the block-length policy lands predictable finishes on block ends, so
  admission waves match too); sampled block decode is reproducible under
  a fixed seed and invariant to the block size (keys advance per
  emission, not per wave).
"""

from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import counters
from repro.configs.base import ArchConfig
from repro.core.artifact import decode_fingerprint, serve_fingerprint
from repro.core.planner import MemoryPlan, plan_graph
from repro.core.unified import (
    PagedStatePlan,
    PlanSession,
    StatePlan,
    UnifiedPlan,
    detect_state_axes,
    plan_paged_state,
    plan_state,
    state_records_from_pytree,
)
from repro.models.api import Model
from repro.runtime.paging import PagedOutOfPagesError
from repro.runtime.residency import (
    BlockOut,
    PytreeAddressing,
    ResidentState,
    residency_enabled,
    state_addressing,
)
from repro.runtime.sampling import (
    SamplingParams,
    TokenSampler,
    greedy_tokens_jit,
    host_probs,
)
from repro.trace.jaxpr_liveness import trace_graph

# Decode-phase host synchronization points, module-wide (the same
# counter discipline as tracer.TRACE_CALLS / planner.PLAN_CALLS /
# unified.STATE_PLAN_CALLS): +1 per host-loop wave, +1 per scan block —
# CI pins host syncs per scan block to exactly 1. Prefill dispatches are
# not counted (they are per-prompt-token by construction; the registry's
# ``decode_dispatches`` counts every decode-program execution).
HOST_SYNCS = 0
# Device-to-host fetches made by the host loop's sampling: +1 per wave
# on both the greedy and the sampled path (the rows of a wave come back
# together, never one slot at a time).
SAMPLE_FETCHES = 0


class WavesExhaustedError(RuntimeError):
    """``run_until_done`` ran out of its wave budget with requests still
    active or queued; ``unfinished`` carries them."""

    def __init__(self, msg: str, unfinished: "list[Request]"):
        super().__init__(msg)
        self.unfinished = unfinished


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    arrived_wave: int = 0
    admitted_wave: int = -1  # wave at which the request took a slot
    tokens: list[int] = dataclasses.field(default_factory=list)
    finished_wave: int = -1
    # host clock (time.perf_counter) at submit, and when the request took
    # a slot, before its prompt feed (None while queued)
    submitted_s: float | None = None
    admitted_s: float | None = None


@dataclasses.dataclass
class _Inflight:
    """A dispatched-but-not-absorbed scan block: the device handles, the
    wave span it covers, the slot->request snapshot at dispatch time, and
    the PREDICTED per-slot waves remaining after it (budget/max_len only —
    EOS can shorten a slot's run but never extend it), which is what the
    chained pre-dispatch sizes the next block from without a host sync."""

    out: BlockOut
    base_wave: int
    length: int
    active_dev: Any  # device bool mask this block was dispatched with
    slots: dict[int, "Request"]
    rem_after: dict[int, int]


@dataclasses.dataclass
class MemoryReport:
    activation_plan: MemoryPlan
    # XLA's temp bytes for the pytree decode step, measured when the
    # bundle was compiled (None without a bundle: the engine compiles no
    # program just to measure it)
    xla_temp_bytes: int | None
    # exact per-slot state bytes — the StatePlan's slot region size
    # (``cache_bytes // n_slots`` used to truncate remainder bytes away)
    cache_bytes_per_slot: int
    n_slots: int
    # the activation plan came from the content-addressed plan cache
    # (repeat engine construction over an unchanged decode graph)
    plan_cache_hit: bool = False
    # where the plan came from: "bundle" (precompiled artifact, zero
    # trace/plan work for both halves), "cache" (plan cache hit), or
    # "planned"
    plan_source: str = "planned"
    # one-line reason when a requested bundle could not be used and the
    # engine fell back to plan-at-construction
    bundle_warning: str | None = None
    # cross-step slot/KV layout (the other half of the unified plan)
    state_plan: StatePlan | None = None
    # planned-vs-live device accounting: with residency on the engine's
    # whole cross-step state is ONE buffer of exactly the planned size
    # (live == planned); off, it is an XLA-allocated pytree whose summed
    # leaf bytes are reported here instead
    state_residency: bool = False
    state_live_bytes: int | None = None
    # v3 zero-compile serving: the AOT executable entries deserialized
    # from the bundle (empty = lazy compile), and the one-line reason
    # when a shipped pack was refused (platform/jax-version/integrity)
    aot_executables: list[str] = dataclasses.field(default_factory=list)
    aot_warning: str | None = None
    # paged state accounting (None on the symmetric backend): pool size,
    # pages currently held by ACTIVE slots, and the page size — under
    # paging ``cache_bytes_per_slot`` above is the HONEST live-page
    # bytes per active slot (pages_live * page_size / n_active), not
    # the symmetric region size (``engine.memory_report`` refreshes the
    # live fields on access)
    state_pages_total: int | None = None
    state_pages_live: int | None = None
    state_page_size: int | None = None

    @property
    def state_planned_bytes(self) -> int | None:
        return (
            self.state_plan.total_size if self.state_plan is not None else None
        )

    @property
    def unified_total_bytes(self) -> int:
        return self.activation_plan.total_size + (
            self.state_plan.total_size if self.state_plan is not None else 0
        )

    def summary(self) -> str:
        lines = [self.activation_plan.summary()]
        if self.bundle_warning:
            lines.append(f"WARNING: {self.bundle_warning}")
        if self.plan_source == "bundle":
            lines.append(
                "activation + state plans served from a precompiled bundle"
            )
        elif self.plan_cache_hit:
            lines.append("activation plan served from the plan cache")
        if self.xla_temp_bytes is not None:
            lines.append(
                f"XLA temp allocation for the same step: "
                f"{self.xla_temp_bytes / 2**20:.3f} MiB"
            )
        if self.aot_executables:
            lines.append(
                f"AOT decode executables: {len(self.aot_executables)} "
                f"loaded from the bundle (zero-compile serving)"
            )
        elif self.aot_warning:
            lines.append(f"WARNING: {self.aot_warning}")
        if self.state_plan is not None:
            lines.append(self.state_plan.summary())
            lines.append(
                f"unified footprint (activation + state): "
                f"{self.unified_total_bytes / 2**20:.3f} MiB"
            )
        if self.state_pages_total is not None:
            live = self.state_pages_live or 0
            page = self.state_page_size or 0
            lines.append(
                f"paged state: {live}/{self.state_pages_total} pool pages "
                f"live ({live * page / 2**20:.3f} MiB of "
                f"{(self.state_planned_bytes or 0) / 2**20:.3f} MiB "
                f"logical)"
            )
        if self.state_live_bytes is not None:
            if self.state_residency and self.state_pages_total is not None:
                lines.append(
                    f"state residency: ON (paged) — live device state "
                    f"{self.state_live_bytes / 2**20:.3f} MiB across "
                    f"page-table-mapped pool pages"
                )
            elif self.state_residency:
                lines.append(
                    f"state residency: ON — live device state "
                    f"{self.state_live_bytes / 2**20:.3f} MiB in one "
                    f"plan-backed allocation"
                )
            else:
                lines.append(
                    f"state residency: off — live device state "
                    f"{self.state_live_bytes / 2**20:.3f} MiB as an "
                    f"XLA-allocated cache pytree"
                )
        lines.append(
            f"KV/state cache: {self.cache_bytes_per_slot / 2**20:.3f} MiB/slot "
            f"x {self.n_slots} slots"
        )
        return "\n".join(lines)


class InferenceEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        *,
        n_slots: int = 4,
        max_len: int = 256,
        session: PlanSession | None = None,
        greedy: bool = True,
        sample_seed: int | None = 0,
        temperature: float = 1.0,
        top_k: int = 0,
        # retire a slot when it emits this token (None = length-only)
        eos_id: int | None = None,
        # decode waves per host sync: 1 = the single-wave host loop
        # (numpy sampling, the oracle); K > 1 = lax.scan block decode
        # with on-device sampling + stop detection
        block_size: int = 1,
        # paged state (None = symmetric max_len slot regions): fixed
        # page size in bytes and pool size in pages (None = enough to
        # map every slot fully); joins the serve fingerprint so paged
        # and symmetric bundles for the same bucket never cross-match
        page_size: int | None = None,
        page_pool: int | None = None,
        # None -> the REPRO_STATE_RESIDENCY env knob (default: on)
        state_residency: bool | None = None,
        # certify the resolved unified plan at startup with the static
        # analyzer (repro.analysis.soundness); None -> the
        # REPRO_STARTUP_LINT env knob (default: off — bundles are gated
        # at publish time, and the tracer/planner are differentially
        # tested, so the per-process re-proof is opt-in paranoia)
        startup_lint: bool | None = None,
    ):
        if cfg.family == "audio":
            raise NotImplementedError("engine drives decoder-only archs")
        self.cfg = cfg
        self.model = Model.for_config(cfg)
        self.params = params
        self.greedy = greedy
        self.session = session
        self.eos_id = None if eos_id is None else int(eos_id)
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.sampling = SamplingParams(
            greedy=greedy, temperature=float(temperature), top_k=int(top_k)
        )
        self.temperature = self.sampling.temperature
        self.top_k = self.sampling.top_k
        # the part of the serve config that shapes the compiled graph —
        # joins the decode fingerprint so bundles self-invalidate across
        # serving configurations (None = default greedy host loop)
        self.page_size = None if not page_size else int(page_size)
        self.page_pool = None if page_pool is None else int(page_pool)
        if self.page_size is not None and self.page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        self._serve_params = serve_fingerprint(
            block_size=self.block_size, greedy=greedy,
            temperature=self.temperature, top_k=self.top_k,
            page_size=self.page_size, page_pool=self.page_pool,
        )
        # ONE engine-owned generator: a per-slot default_rng(self._wave)
        # gave every slot in a wave the same seed, so slots with identical
        # logits always emitted identical tokens and reruns were trivially
        # correlated
        self._sampler = np.random.default_rng(sample_seed)
        self._sample_seed = sample_seed

        # --- the unified plan for this serving bucket -------------------
        # The session is the single plan source: a precompiled v2 bundle
        # carries BOTH halves (activation offsets + cross-step state
        # layout) behind one fingerprint check — no jaxpr trace, no
        # planner call, no state-layout work, no XLA memory-analysis
        # compile. Nearest-bucket selection may hand back a larger
        # compiled max_len than requested; the engine serves that bucket.
        # Any mismatch or load failure falls back to plan-at-construction
        # with a one-line warning. Auto-selection may also hand back a
        # wider slot pool (n_slots >= requested — a bigger §4 shared-object
        # pool is admissible, just wasteful); the engine serves that pool.
        resolution = (
            session.resolve(
                cfg, n_slots=n_slots, max_len=max_len,
                serve_params=self._serve_params,
            )
            if session is not None
            else None
        )
        self.max_len = resolution.max_len if resolution is not None else max_len
        if resolution is not None and resolution.n_slots:
            n_slots = resolution.n_slots
        self.n_slots = n_slots

        # Shape-level cache template: structure + shapes + dtypes for
        # tracing, state planning, and the residency binding. No state
        # buffer is materialized until the backend is chosen below — the
        # residency path must never allocate the pytree AND the arena.
        cache_template = jax.eval_shape(
            lambda: self.model.init_cache(n_slots, self.max_len)
        )

        def _decode_fn(p, t, c, pos, act):
            return self.model.decode_step(p, t, c, pos, active=act)

        bundle = resolution.bundle if resolution is not None else None
        unified = resolution.unified if resolution is not None else None
        bundle_warning = resolution.warning if resolution is not None else None
        spec = resolution.spec if resolution is not None else None
        tok0 = jnp.zeros((n_slots, 1), jnp.int32)
        pos0 = jnp.zeros((n_slots,), jnp.int32)
        act0 = jnp.ones((n_slots,), bool)
        if bundle is not None and session is not None and session.verify_graph:
            # trace-backed verification: the config fingerprint cannot see
            # model-code changes (only a PIPELINE_REVISION bump can), so a
            # paranoid caller trades the zero-trace cold start for a
            # structural check of the stored graph_fingerprint
            from repro.core.artifact import graph_fingerprint

            fresh = graph_fingerprint(trace_graph(
                _decode_fn,
                params, tok0, cache_template, pos0, act0,
                name=f"{cfg.name}-decode",
            ))
            if bundle.graph_fingerprint != fresh:
                bundle_warning = (
                    f"plan bundle graph fingerprint mismatch (bundle "
                    f"{str(bundle.graph_fingerprint)[:12]}, traced "
                    f"{fresh[:12]} — model code changed since compile?); "
                    f"planned at construction instead"
                )
                bundle = None
                unified = None

        xla_temp: int | None = None
        if unified is not None and unified.activation is not None:
            plan = unified.activation
            if bundle is not None:
                plan_source = "bundle"
                xla_temp = bundle.provenance.get("xla_temp_bytes")
            else:
                plan_source = "cache" if plan.cache_hit else "planned"
        else:
            # fallback half: a pre-searched graph (core/order_search,
            # core/fusion_search) from the spec can be planned directly
            # instead of tracing the default-order step
            graph = (
                spec.graph
                if spec is not None and spec.graph is not None
                else trace_graph(
                    _decode_fn,
                    params, tok0, cache_template, pos0, act0,
                    name=f"{cfg.name}-decode",
                )
            )
            strategy = spec.strategy if spec is not None else "auto"
            plan = plan_graph(graph, mode="offsets", strategy=strategy)
            plan_source = "cache" if plan.cache_hit else "planned"
        # cross-step half: a v2 bundle ships the slot/KV layout; anything
        # else lays it out from the engine's own cache pytree (cheap, but
        # counted — unified.STATE_PLAN_CALLS — so tests can pin the
        # bundle path to zero work here too)
        if unified is not None and unified.state is not None:
            state_plan = unified.state
        elif self.page_size:
            state_plan = plan_paged_state(
                state_records_from_pytree(cache_template, n_slots=n_slots),
                n_slots=n_slots,
                max_len=self.max_len,
                page_size=self.page_size,
                page_pool=self.page_pool,
                axes=detect_state_axes(
                    self.model.init_cache,
                    n_slots=n_slots,
                    max_len=self.max_len,
                ),
            )
        else:
            state_plan = plan_state(
                state_records_from_pytree(cache_template, n_slots=n_slots),
                n_slots=n_slots,
                max_len=self.max_len,
            )
        self.unified_plan = UnifiedPlan(
            activation=plan,
            state=state_plan,
            fingerprint=(
                unified.fingerprint
                if unified is not None
                else decode_fingerprint(
                    cfg, n_slots=n_slots, max_len=self.max_len,
                    serve_params=self._serve_params,
                )
            ),
        )
        if (
            startup_lint
            if startup_lint is not None
            else os.environ.get("REPRO_STARTUP_LINT", "").lower()
            in ("1", "on", "true")
        ):
            from repro.analysis import LintGateError, soundness
            from repro.analysis.findings import Report

            report = Report().extend(
                soundness.certify_unified(
                    self.unified_plan, label=f"{cfg.name}-startup"
                ),
                checked=f"{cfg.name}-startup",
            )
            if not report.ok():
                raise LintGateError(
                    report, context="startup lint refused the unified plan"
                )

        self.plan_bundle = bundle
        # v3 zero-compile path: deserialize the bundle's AOT executables
        # (when shipped) for the state backend below — decode/reset/scan
        # block then dispatch without a single XLA compile. A refused
        # pack (wrong platform, different jax version, integrity failure)
        # warns ONE line and serves through the counted lazy jits — the
        # same degradation a v2 bundle gets.
        aot_execs: dict[str, Any] = {}
        aot_warning: str | None = None
        if bundle is not None and bundle.executables is not None:
            from repro.runtime.aot import load_executables

            aot_execs, aot_warning = load_executables(bundle)
            if aot_warning:
                warnings.warn(aot_warning, RuntimeWarning, stacklevel=2)
        # allocate-once deployment: BOTH layouts come from the one unified
        # plan. The cross-step state is materialized by the backend: with
        # residency on, ONE device buffer laid out by the plan (flat, or
        # the page pool under a paged plan), donate-threaded through every
        # program; off, the XLA-allocated cache pytree (the reference),
        # which has no page indirection, so serving it would silently
        # drop the paging that was asked for.
        self.activation_layout, self.state_layout = (
            self.unified_plan.arena_layouts()
        )
        if residency_enabled(state_residency):
            addressing = state_addressing(
                state_plan, cache_template, n_slots=n_slots,
                layout=self.state_layout,
            )
        elif isinstance(state_plan, PagedStatePlan):
            raise ValueError(
                "paged state requires state residency; serve with "
                "residency on, or drop page_size"
            )
        else:
            addressing = PytreeAddressing(
                self.model, n_slots=n_slots, max_len=self.max_len
            )
        # on the resident paths the state is zero-initialized straight
        # into the buffer (init_cache's contract is all-zero state): the
        # engine NEVER materializes a cache pytree there, so cold start
        # holds exactly one state allocation, not pytree + arena
        self.state = ResidentState(
            self.model, addressing, executables=aot_execs
        )
        pages = self.state.pages
        self._memory_report = MemoryReport(
            activation_plan=plan,
            xla_temp_bytes=xla_temp,
            cache_bytes_per_slot=(
                state_plan.bytes_per_slot if pages is None else 0
            ),
            n_slots=n_slots,
            plan_cache_hit=plan.cache_hit,
            plan_source=plan_source,
            bundle_warning=bundle_warning,
            state_plan=state_plan,
            state_residency=self.state.residency,
            state_live_bytes=self.state.live_bytes,
            aot_executables=sorted(aot_execs),
            aot_warning=aot_warning,
            state_pages_total=None if pages is None else pages.total,
            state_pages_live=None if pages is None else pages.live,
            state_page_size=None if pages is None else pages.plan.page_size,
        )

        # serving state — per-slot positions (continuous batching: every
        # slot advances at its own position in ONE decode call per wave)
        self._queue: list[Request] = []
        self._active: dict[int, Request] = {}  # slot -> request
        self._slot_pos = np.zeros(n_slots, np.int32)
        self._slot_tokens = np.zeros((n_slots, 1), np.int32)
        self._wave = 0
        # slot occupancy intervals for the §4-style shared-objects audit:
        # (slot, first_wave, last_wave, request_id)
        self.slot_log: list[tuple[int, int, int, int]] = []
        self._next_rid = 0
        # scan-block serving state: the on-device sampler (closed over by
        # the block jit), per-slot PRNG keys (lazy — only the block path
        # or on-device sampling needs them), and the block counter the
        # throughput bench pairs with HOST_SYNCS
        self._token_sampler = TokenSampler(self.sampling, max_len=self.max_len)
        self._keys = None
        self.n_blocks = 0

    # ------------------------------------------------------------ admin
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(
            Request(rid, np.asarray(prompt, np.int32), max_new_tokens,
                    arrived_wave=self._wave, submitted_s=time.perf_counter())
        )
        return rid

    @property
    def caches(self):
        """The live cache pytree — concrete XLA buffers with residency
        off, views over the one state buffer with it on (inspection
        only; the serving path never materializes this)."""
        return self.state.caches

    @property
    def memory_report(self) -> MemoryReport:
        """The planned-vs-live report. Under paging the live fields are
        refreshed on access — ``cache_bytes_per_slot`` is the HONEST
        live-page bytes per active slot and ``state_pages_live`` /
        ``state_live_bytes`` track the pool — so the report tells the
        truth mid-serve, not just at construction."""
        rep = self._memory_report
        if self.state.pages is None:
            return rep
        return dataclasses.replace(
            rep,
            cache_bytes_per_slot=(
                self.state.live_bytes // max(len(self._active), 1)
            ),
            state_pages_live=self.state.pages.live,
            state_live_bytes=self.state.live_bytes,
        )

    @property
    def page_log(self) -> list[tuple[int, int, int, int]]:
        """Page occupancy intervals ``(page, admitted_wave,
        finished_wave, request_id)`` — the page-granular twin of
        ``slot_log`` (empty where the state is not paged), audited by
        ``shared_objects.from_page_log``."""
        pages = self.state.pages
        return [] if pages is None else list(pages.log)

    def _step_tokens(self, tokens: np.ndarray, pos: np.ndarray,
                     active: np.ndarray):
        # jnp.array COPIES (jnp.asarray is zero-copy on CPU, and the engine
        # mutates these numpy buffers while the async dispatch may still be
        # reading them — a real data race, found as a nondeterministic
        # wrong-token bug on the slowest arch).
        #
        # The state backend synchronizes on its new state before returning:
        # with async dispatch left in flight we observed rare
        # nondeterministic state corruption on CPU (two stable token
        # trajectories from identical inputs; forcing completion removes
        # it). The engine is host-latency-bound at reference scale, so
        # this costs nothing; a production engine would double-buffer.
        return self.state.decode(
            self.params, jnp.array(tokens),
            jnp.array(pos, jnp.int32), jnp.array(active),
        )

    def _take_slots(self) -> list[tuple[int, Request]]:
        """Give queued requests the free slots, in FIFO order; returns
        the ``(slot, request)`` pairs admitted."""
        free = [s for s in range(self.n_slots) if s not in self._active]
        taken: list[tuple[int, Request]] = []
        while free and self._queue:
            # admit before touching any slot state: the paged state maps
            # the pages the head request needs (its cache never grows
            # past prompt + budget, capped by the bucket length). A
            # refused allocation mutates nothing: with active slots we
            # stop admitting and retry after the next retirement returns
            # pages (FIFO head-of-line, so the admission schedule stays
            # deterministic); with NO active slots the whole pool is
            # free, so the request can never fit this bucket and the
            # error propagates.
            req = self._queue[0]
            try:
                self.state.admit(
                    free[0],
                    min(len(req.prompt) + req.max_new_tokens, self.max_len),
                    rid=req.request_id, wave=self._wave,
                )
            except PagedOutOfPagesError:
                if self._active:
                    break
                raise
            slot = free.pop(0)
            self._queue.pop(0)
            req.admitted_wave = self._wave
            req.admitted_s = time.perf_counter()
            self._active[slot] = req
            taken.append((slot, req))
        return taken

    def _admit(self) -> None:
        taken = self._take_slots()
        if not taken:
            return
        with counters.span("repro.admit", admitted=len(taken)):
            for slot, req in taken:
                # per-slot prefill: feed prompt tokens through the decode
                # step at this slot's own position; other slots are NOT
                # advanced (their position/token stay put -> the scatter
                # rewrites their current cache entry with identical
                # values: idempotent).
                self._slot_pos[slot] = 0
                only_this = np.zeros(self.n_slots, bool)
                only_this[slot] = True
                # wipe the recycled slot's state (stale SSM state would
                # leak); the backend copies the keep mask — see
                # _step_tokens race note
                self.state.reset(~only_this)
                with counters.span("repro.prompt_feed", rid=req.request_id,
                                   tokens=len(req.prompt) - 1):
                    for t in req.prompt[:-1]:
                        self._slot_tokens[slot, 0] = t
                        self._step_tokens(
                            self._slot_tokens, self._slot_pos, only_this
                        )
                        self._slot_pos[slot] += 1
                self._slot_tokens[slot, 0] = req.prompt[-1]

    def _fetch_rows(self, logits) -> np.ndarray:
        """The wave's one device-to-host fetch for sampling, row ``slot``
        for each slot: greedy, the device's pick (``(n_slots, 1)``
        int32 — one argmax dispatch, ``4 * n_slots`` bytes back);
        sampled, the whole ``(n_slots, vocab)`` logits."""
        global SAMPLE_FETCHES
        SAMPLE_FETCHES += 1
        if self.greedy:
            return np.asarray(greedy_tokens_jit(logits))[:, None]
        return np.asarray(logits)

    def _sample_token(self, row: np.ndarray) -> int:
        """One slot's next token from its row of :meth:`_fetch_rows`:
        greedy, the pick the device already took (a one-element row);
        sampled, a draw from the logits row with the engine-owned
        generator (so consecutive draws — e.g. two slots in one wave —
        are independent, while a fixed ``sample_seed`` keeps whole runs
        reproducible). Probabilities come from the float64
        ``sampling.host_probs`` — the float32 softmax tripped
        ``Generator.choice``'s sum-to-1 check on rounding."""
        if self.greedy:
            return int(row[0])
        p = host_probs(row, temperature=self.temperature, top_k=self.top_k)
        return int(self._sampler.choice(len(p), p=p))

    def _finished(self, req: Request, slot: int, nxt: int) -> bool:
        """The retirement oracle, shared by the host loop and the block
        absorber (the on-device stop detection mirrors exactly this):
        EOS, exhausted new-token budget, or the context limit."""
        return (
            (self.eos_id is not None and nxt == self.eos_id)
            or len(req.tokens) >= req.max_new_tokens
            or int(self._slot_pos[slot]) >= self.max_len - 1
        )

    # ------------------------------------------------------------ serve
    def step(self) -> list[Request]:
        """One decode wave over all active slots; returns finished reqs.
        Host spans: ``repro.step`` (``active``: slots holding a request
        as the step starts) around it all, then ``repro.admit``, and
        ``repro.sample`` (``rows``: active slots) around the wave's
        sampling fetch and the per-slot bookkeeping after its dispatch."""
        with counters.span("repro.step", active=len(self._active)):
            return self._step()

    def _step(self) -> list[Request]:
        global HOST_SYNCS
        self._admit()
        if not self._active:
            return []
        active = np.zeros(self.n_slots, bool)
        for s in self._active:
            active[s] = True
        logits = self._step_tokens(self._slot_tokens, self._slot_pos, active)
        HOST_SYNCS += 1
        finished: list[Request] = []
        with counters.span("repro.sample", rows=len(self._active)):
            rows = self._fetch_rows(logits)
            for slot, req in list(self._active.items()):
                nxt = self._sample_token(rows[slot])
                req.tokens.append(nxt)
                self._slot_tokens[slot, 0] = nxt
                self._slot_pos[slot] += 1
                if self._finished(req, slot, nxt):
                    req.finished_wave = self._wave
                    self.slot_log.append(
                        (slot, req.admitted_wave, self._wave, req.request_id)
                    )
                    finished.append(req)
                    del self._active[slot]
                    self.state.retire(slot, self._wave)
        self._wave += 1
        return finished

    # ----------------------------------------------------- block serve
    def _ensure_keys(self):
        if self._keys is None:
            seed = (
                self._sample_seed
                if self._sample_seed is not None
                else int(np.random.default_rng().integers(2**31 - 1))
            )
            self._keys = self._token_sampler.init_keys(seed, self.n_slots)
        return self._keys

    def _remaining_waves(self) -> dict[int, int]:
        """Per-active-slot PREDICTABLE waves left (new-token budget and
        max_len; EOS can only shorten a run, never extend it)."""
        rem = {}
        for slot, req in self._active.items():
            budget = req.max_new_tokens - len(req.tokens)
            len_cap = max((self.max_len - 1) - int(self._slot_pos[slot]), 1)
            rem[slot] = max(min(budget, len_cap), 1)
        return rem

    def _plan_block(self, waves_left: int | None = None) -> int:
        """This block's scan length K: capped by the LONGEST predictable
        remaining run (no all-frozen tail waves) and — when requests are
        queued — by the SHORTEST one, so predictable finishes land on the
        block's last wave and admission happens at exactly the same wave
        as the single-wave host loop (the differential-test schedule
        contract). A mid-block EOS still freezes its slot until the block
        ends; with a non-empty queue that defers the slot's re-admission
        by < block_size waves (the one scheduling deviation from the
        host loop — tokens are unaffected)."""
        rem = self._remaining_waves()
        k = min(self.block_size, max(rem.values()))
        if self._queue:
            k = min(k, min(rem.values()))
        if waves_left is not None:
            k = min(k, waves_left)
        return max(k, 1)

    def _dispatch_block(self, k: int) -> _Inflight:
        """Launch K scan waves WITHOUT a host sync. Every input is copied
        to a fresh device array before dispatch — the host keeps mutating
        its numpy mirrors while the block is in flight (the _step_tokens
        race note, applied to the async path)."""
        active = np.zeros(self.n_slots, bool)
        budget = np.zeros(self.n_slots, np.int32)
        rem = self._remaining_waves()
        for slot, req in self._active.items():
            active[slot] = True
            budget[slot] = req.max_new_tokens - len(req.tokens)
        active_dev = jnp.array(active)
        out = self.state.decode_block(
            self.params,
            jnp.array(self._slot_tokens),
            jnp.array(self._slot_pos, jnp.int32),
            active_dev,
            jnp.zeros(self.n_slots, bool),
            jnp.array(budget),
            self._ensure_keys(),
            jnp.int32(-1 if self.eos_id is None else self.eos_id),
            length=k,
            sampler=self._token_sampler,
        )
        self._keys = out.keys
        return _Inflight(
            out=out, base_wave=self._wave, length=k, active_dev=active_dev,
            slots=dict(self._active),
            rem_after={s: max(r - k, 0) for s, r in rem.items()},
        )

    def _dispatch_chained(self, prev: _Inflight, k: int) -> _Inflight:
        """Launch the NEXT block off the in-flight block's device carry —
        no host sync between the two dispatches. Only valid when nothing
        is queued (the carry's ``done`` mask already freezes every slot
        that finished mid-stream, and no admission can be pending)."""
        out = self.state.decode_block(
            self.params, prev.out.tokens, prev.out.pos, prev.active_dev,
            prev.out.done, prev.out.budget, self._keys,
            jnp.int32(-1 if self.eos_id is None else self.eos_id),
            length=k, sampler=self._token_sampler,
        )
        self._keys = out.keys
        return _Inflight(
            out=out, base_wave=prev.base_wave + prev.length, length=k,
            active_dev=prev.active_dev, slots=prev.slots,
            rem_after={s: max(r - k, 0) for s, r in prev.rem_after.items()},
        )

    def _absorb_block(self, inflight: _Inflight) -> list[Request]:
        """Fetch one block's per-wave outputs (THE one host sync per
        block) and replay them through the host bookkeeping — the same
        retirement oracle as the host loop, wave by wave, so slot_log
        intervals and finish waves mean the same thing in both modes."""
        global HOST_SYNCS
        HOST_SYNCS += 1
        self.n_blocks += 1
        toks = np.asarray(inflight.out.wave_tokens)
        emitted = np.asarray(inflight.out.emitted)
        finished: list[Request] = []
        for k in range(inflight.length):
            wave = inflight.base_wave + k
            for slot, req in inflight.slots.items():
                if self._active.get(slot) is not req or not emitted[k, slot]:
                    continue
                nxt = int(toks[k, slot])
                req.tokens.append(nxt)
                self._slot_tokens[slot, 0] = nxt
                self._slot_pos[slot] += 1
                if self._finished(req, slot, nxt):
                    req.finished_wave = wave
                    self.slot_log.append(
                        (slot, req.admitted_wave, wave, req.request_id)
                    )
                    finished.append(req)
                    del self._active[slot]
                    self.state.retire(slot, wave)
        self._wave = inflight.base_wave + inflight.length
        return finished

    def step_block(self) -> list[Request]:
        """One synchronous scan block: admit, dispatch K waves, absorb.
        (``run_until_done`` pipelines these — it chains the next block's
        dispatch before fetching the previous block's results whenever
        the queue is empty.) Under the host span ``repro.step``."""
        with counters.span("repro.step", active=len(self._active)):
            self._admit()
            if not self._active:
                return []
            return self._absorb_block(
                self._dispatch_block(self._plan_block())
            )

    def _run_blocks(self, max_waves: int) -> list[Request]:
        done: list[Request] = []
        waves_left = max_waves
        inflight: _Inflight | None = None
        while True:
            if inflight is None:
                self._admit()
                if not self._active or waves_left <= 0:
                    break
                k = self._plan_block(waves_left)
                inflight = self._dispatch_block(k)
                waves_left -= k
            # async admission/retirement: with nothing queued, no host
            # decision can change the next block's inputs — chain its
            # dispatch off the in-flight carry BEFORE fetching, so the
            # absorb below overlaps device compute
            nxt: _Inflight | None = None
            if not self._queue and waves_left > 0:
                rem = [r for r in inflight.rem_after.values() if r > 0]
                if rem:
                    k2 = min(self.block_size, max(rem), waves_left)
                    nxt = self._dispatch_chained(inflight, k2)
                    waves_left -= k2
            done.extend(self._absorb_block(inflight))
            inflight = nxt
            if inflight is None and not self._active and not self._queue:
                break
        return done

    def unfinished_requests(self) -> list[Request]:
        """Requests still holding a slot or waiting in the queue —
        surfaced when ``run_until_done`` exhausts its wave budget."""
        return list(self._active.values()) + list(self._queue)

    def run_until_done(
        self, max_waves: int = 10_000, *, raise_on_exhausted: bool = False
    ) -> list[Request]:
        """Serve until queue and slots drain (or ``max_waves`` decode
        waves run). Exhausting the wave budget with work remaining warns
        — or raises :class:`WavesExhaustedError` with the unfinished
        requests attached under ``raise_on_exhausted=True`` — instead of
        silently returning partial results."""
        done: list[Request] = []
        if self.block_size <= 1:
            for _ in range(max_waves):
                done.extend(self.step())
                if not self._active and not self._queue:
                    break
        else:
            done.extend(self._run_blocks(max_waves))
        if self._active or self._queue:
            msg = (
                f"run_until_done exhausted max_waves={max_waves} with "
                f"{len(self._active)} active and {len(self._queue)} queued "
                f"request(s) unfinished"
            )
            if raise_on_exhausted:
                raise WavesExhaustedError(msg, self.unfinished_requests())
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return done
