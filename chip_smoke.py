"""Chip smoke: serve qwen3-0.6b at full width on one TPU through the
normal entry point, ``repro.launch.serve.run``.

Every phase serves 8 requests (prompt 32, 16 new greedy tokens) at 8
slots and ``max_len`` 4096, with random weights from seed 0, in this one
process — the only one that touches JAX:

  (a) the default resident state backend on the host loop;
  (b) the same with ``--block-size 8`` (scan-block decode);
  (c) the paged backend, ``--page-size 1048576``;
  (d) ``REPRO_STATE_RESIDENCY=off`` (the cache pytree — the reference);
  (d8) the cache pytree with ``--block-size 8`` (the reference for (b));
  (e) ``--compile-first`` into a fresh bundle directory, then a second
      serve from that bundle, which must compile no decode program.

Checks (each failure exits non-zero): every request returns exactly the
requested token count; the resident backend's live state bytes equal its
planned bytes; (a), (c) and (e) emit exactly (d)'s greedy tokens, and
(b) exactly (d8)'s, since each pair runs the same decode step and
differs only in how the state is addressed; the bundle serve loads its
AOT executables without a warning and pays zero decode compiles.

The scan block is another program than the single-step decode: its body
fuses the model differently, and the logits are bf16, so a near tie can
round the other way. Where (b) differs from (a), the divergence is
printed, not failed: (b) is held to (d8), which runs its program.

Wall times printed here are smoke timings, not benchmark numbers. The
last line of a passing run on a TPU is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Usage::

    python chip_smoke.py                          # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-run

``--dry-run`` runs every phase and check at the reduced qwen3-0.6b config
on whatever device JAX finds, then exits 3 without reporting a result.
Without it, a machine whose first JAX device is not a TPU exits 2 before
serving anything.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FULL = dict(slots=8, max_len=4096, requests=8, prompt_len=32, max_new=16,
            page_size=1 << 20)
# reduced config for --dry-run: slot reuse and several pages per slot
DRY = dict(slots=4, max_len=64, requests=6, prompt_len=8, max_new=6,
           page_size=1024)
BLOCK = 8


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class _CompileMeter:
    """Backend compile seconds (cache reads included), and compiles that
    asked the persistent cache and found their executable there, read
    from JAX's monitoring events (cumulative; phases take deltas). JAX
    writes only compiles longer than a second to the cache."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.requests, self.hits


def _divergence(got: dict, want: dict) -> list[str]:
    """One line per request whose tokens differ: where and how."""
    lines = []
    for rid in sorted(set(got) | set(want)):
        a, b = got.get(rid), want.get(rid)
        if a == b:
            continue
        if a is None or b is None:
            lines.append(f"request {rid}: present in only one run")
            continue
        first = next(i for i, (x, y) in enumerate(zip(a + [None], b + [None]))
                     if x != y)
        lines.append(f"request {rid}: first differs at token {first} "
                     f"({a[first:first + 4]} vs {b[first:first + 4]})")
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="run every phase and check at the reduced config "
                         "on any device, then exit 3 without a result")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if dev.platform != "tpu" and not args.dry_run:
        print("chip_smoke.py: the first JAX device is not a TPU; this smoke "
              "has no CPU fallback (rehearse with --dry-run)",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import serve
    from repro.launch.jax_cache import enable_compile_cache
    from repro.runtime import residency

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # nothing left in the checkout or the environment feeds the run: no
    # on-disk plan cache, and the state backend is chosen per phase
    os.environ.pop("REPRO_PLAN_CACHE_DIR", None)
    os.environ.pop("REPRO_STATE_RESIDENCY", None)
    meter = _CompileMeter(jax.monitoring)
    size = DRY if args.dry_run else FULL
    base = ["--arch", "qwen3-0.6b", "--slots", str(size["slots"]),
            "--max-len", str(size["max_len"]),
            "--requests", str(size["requests"]),
            "--prompt-len", str(size["prompt_len"]),
            "--max-new", str(size["max_new"])]
    if not args.dry_run:
        base.insert(0, "--full")

    def phase(name: str, extra: list[str], env: dict | None = None) -> dict:
        saved = {k: os.environ.get(k) for k in env or {}}
        os.environ.update(env or {})
        c0 = residency.COMPILE_CALLS
        s0, r0, h0 = meter.snapshot()
        t0 = time.perf_counter()
        try:
            out = serve.run(base + extra)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        wall = time.perf_counter() - t0
        s1, r1, h1 = meter.snapshot()
        out["decode_compiles"] = residency.COMPILE_CALLS - c0
        gc.collect()
        stats = dev.memory_stats() or {}
        print(f"[smoke {name}] {' '.join(extra) or 'default'}"
              f"{' ' + str(env) if env else ''}: smoke wall {wall:.2f} s "
              f"(smoke timing, not a benchmark), backend compile "
              f"{s1 - s0:.2f} s, persistent cache hits {h1 - h0} of "
              f"{r1 - r0} compiles, decode compiles {out['decode_compiles']}, "
              f"cold start {out['cold_start_s']:.2f} s, plan source "
              f"{out['plan_source']}, peak device bytes in use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)
        toks = out["tokens_per_request"]
        _check(len(toks) == size["requests"],
               f"({name}) served {len(toks)} of {size['requests']} requests")
        _check(all(len(t) == size["max_new"] for t in toks.values()),
               f"({name}) token counts {[len(t) for t in toks.values()]} "
               f"!= {size['max_new']}")
        return out

    a = phase("a", [])
    b = phase("b", ["--block-size", str(BLOCK)])
    c = phase("c", ["--page-size", str(size["page_size"])])
    d = phase("d", [], {"REPRO_STATE_RESIDENCY": "off"})
    d8 = phase("d8", ["--block-size", str(BLOCK)],
               {"REPRO_STATE_RESIDENCY": "off"})
    with tempfile.TemporaryDirectory(prefix="chip_smoke_bundle_") as bundle:
        e1 = phase("e1", ["--compile-first", "--plan-bundle", bundle])
        e2 = phase("e2", ["--plan-bundle", bundle])

    for name, out in (("a", a), ("b", b), ("e1", e1), ("e2", e2)):
        _check(out["state_residency"] and not out["page_size"],
               f"({name}) did not serve the resident backend")
        _check(out["state_live_bytes"] == out["state_planned_bytes"],
               f"({name}) live state {out['state_live_bytes']} B != planned "
               f"{out['state_planned_bytes']} B")
    _check(c["page_size"] == size["page_size"] and c["state_residency"],
           "(c) did not serve the paged backend")
    for name, out in (("d", d), ("d8", d8)):
        _check(not out["state_residency"],
               f"({name}) did not serve the pytree backend")
    for name, out in (("e1", e1), ("e2", e2)):
        _check(out["plan_source"] == "bundle",
               f"({name}) plan source {out['plan_source']!r}: "
               f"{out['bundle_warning']}")
        _check(out["aot_warning"] is None,
               f"({name}) AOT pack refused: {out['aot_warning']}")
    _check(e2["decode_compiles"] == 0,
           f"(e2) paid {e2['decode_compiles']} decode compiles serving "
           f"from the bundle")

    failed = []
    for name, out, ref_name, ref in (
        ("a", a, "d", d), ("c", c, "d", d), ("e1", e1, "d", d),
        ("e2", e2, "d", d), ("b", b, "d8", d8),
    ):
        lines = _divergence(out["tokens_per_request"],
                            ref["tokens_per_request"])
        for line in lines:
            print(f"[smoke {name}] diverges from ({ref_name}): {line}")
        if lines:
            failed.append(name)
    _check(not failed, f"greedy tokens differ in phase(s) {failed}")
    # the scan block against the host loop: printed, not held (see the
    # module docstring)
    for line in _divergence(b["tokens_per_request"], a["tokens_per_request"]):
        print(f"[smoke b] differs from (a): {line}")
    print(f"[smoke] all phases passed; greedy tokens of (a), (c), (e) match "
          f"(d) and those of (b) match (d8); request 0: "
          f"{d['tokens_per_request'][0][:8]}...", flush=True)

    if args.dry_run:
        print("dry run: every phase and check passed on "
              f"{device['platform']}; no chip result is reported")
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
