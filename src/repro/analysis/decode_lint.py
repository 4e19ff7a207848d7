"""Static lint of the compiled decode step and scan block.

The serving invariants — the residency buffer's donation really aliases
input to output, decode never round-trips through the host, the scan
block is a single rolled loop — were previously only observable at
runtime (HOST_SYNCS deltas, ``live_bytes`` checks). This pass proves
them ahead of time from the compiled executable's HLO text:

* **donation aliasing** — the state parameter (the one of the state
  buffer's exact type, e.g. ``bf16[939524096]``) must appear in the
  module's ``input_output_alias`` table; a silently dropped donation
  doubles peak state memory and breaks the planned-layout-is-live-layout
  contract (error);
* **host transfers** — no outfeed/infeed/send/recv, no host memory
  space (``S(5)``) shapes, no host-placement custom-calls (error);
* **state-buffer copies/converts** — plain ``copy``/``convert`` ops of
  the whole state buffer's type. The decode program updates the
  buffer in place (row scatters and layer stores into the loop's
  carry), so none is expected; the CPU backend may still copy the
  carried buffer around an in-place store inside a loop (a Mamba
  layer's state store), where the TPU program of the same step has no
  such copy, so these report as warnings with their location, not
  errors;
* **scan shape** — the block must lower to one ``while`` with the
  expected known trip count; a missing loop means XLA unrolled (and
  rematerialized) the body, a wrong count means the block traced at the
  wrong length (error).

Programs are lowered shape-level (``jax.eval_shape`` for params; no
weights are materialized) through the *same* program factories the
serving backend jits (``runtime/residency.decode_impl`` & co.), so the
lint inspects the real decode program, not a stand-in.

:func:`lint_executables` applies the same checks to a v3 bundle's
AOT-serialized executables *after* deserialization — proving the
donation aliasing (and the absence of host transfers) survived the
serialize→bundle→deserialize round trip, which is the publish gate's
last step before a pack ships.
"""

from __future__ import annotations

import dataclasses
import re

from repro.analysis.findings import Finding, Report

PASS = "decode_lint"

_HOST_OPCODES = {
    "outfeed", "infeed", "send", "recv", "send-done", "recv-done",
}
# custom-call targets that move data to host memory
_HOST_CALL_RE = re.compile(r"MoveToHost|PinToHost|annotate_device_placement")
_HOST_SPACE_RE = re.compile(r"S\(5\)")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(may-alias|must-alias)\)"
)
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")


def _finding(code, message, where="", severity="error") -> Finding:
    return Finding(
        pass_name=PASS, code=code, message=message, where=where,
        severity=severity,
    )


def _called_name(inst) -> str | None:
    m = re.search(r"calls=(%[\w.\-]+)", inst.attrs)
    return m.group(1) if m else None


def parse_alias_table(hlo_text: str) -> list[tuple[tuple[int, ...], int, str]]:
    """The module-level ``input_output_alias`` table:
    [(output index, parameter number, kind)]."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias=")
    depth = 0
    j = i
    while j < len(hlo_text):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    block = hlo_text[i : j + 1]
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(block):
        idx = tuple(
            int(x) for x in m.group(1).replace(" ", "").split(",") if x
        )
        out.append((idx, int(m.group(2)), m.group(3)))
    return out


@dataclasses.dataclass
class DecodeProgram:
    """One lowered+compiled decode program ready for linting."""

    label: str  # e.g. "qwen3-0.6b:step" / "qwen3-0.6b:block8"
    hlo: str  # compiled.as_text()
    state_type: str  # HLO type of the state buffer, e.g. "f32[16384]"
    expect_trip: int | None = None  # scan length for block programs


_HLO_DTYPES = {
    "bool": "pred", "int8": "s8", "uint8": "u8", "int16": "s16",
    "uint16": "u16", "int32": "s32", "uint32": "u32", "int64": "s64",
    "uint64": "u64", "bfloat16": "bf16", "float16": "f16",
    "float32": "f32", "float64": "f64",
}


def hlo_type(aval) -> str:
    """``aval``'s HLO type without layout, e.g. ``bf16[8,128]``."""
    import numpy as np

    dims = ",".join(str(int(d)) for d in aval.shape)
    return f"{_HLO_DTYPES[np.dtype(aval.dtype).name]}[{dims}]"


def lint_program(prog: DecodeProgram) -> list[Finding]:
    """All static checks over one compiled decode program's HLO."""
    from repro.launch.hlo_analysis import parse_hlo

    def is_state(type_str: str) -> bool:
        return type_str.split("{", 1)[0] == prog.state_type

    findings: list[Finding] = []
    comps, entry = parse_hlo(prog.hlo)
    if entry is None:
        return [_finding("hlo-unparseable", "no entry computation found",
                         prog.label)]

    # --- the state buffer parameter and its donation
    state_params = [
        int(inst.raw_operands)
        for inst in comps[entry].instructions
        if inst.opcode == "parameter" and is_state(inst.result_type)
    ]
    if not state_params:
        findings.append(
            _finding(
                "state-param-missing",
                f"no {prog.state_type} parameter in the entry "
                f"computation — the state buffer is not an input of the "
                f"compiled program",
                prog.label,
            )
        )
    aliased = {param for _idx, param, _kind in parse_alias_table(prog.hlo)}
    for param in state_params:
        if param not in aliased:
            findings.append(
                _finding(
                    "state-not-donated",
                    f"state buffer (parameter {param}, "
                    f"{prog.state_type}) absent from the "
                    f"input_output_alias table: donation did not alias, "
                    f"decode double-buffers the whole state",
                    prog.label,
                )
            )

    # --- host transfers + whole-buffer copies/converts, everywhere.
    # Copies/converts inside fusion bodies stay in registers/VMEM (see
    # hlo_analysis byte accounting) — only un-fused ones materialize, so
    # only those are scanned; while bodies/conds are not exempt.
    fusion_bodies = {
        _called_name(inst)
        for comp in comps.values()
        for inst in comp.instructions
        if inst.opcode == "fusion"
    }
    copy_sites: list[str] = []
    for comp in comps.values():
        for inst in comp.instructions:
            where = f"{prog.label}:{comp.name}{inst.name}"
            if inst.opcode in _HOST_OPCODES:
                findings.append(
                    _finding(
                        "host-transfer",
                        f"{inst.opcode} in compiled decode — device/host "
                        f"round-trip inside the hot path",
                        where,
                    )
                )
            elif inst.opcode == "custom-call" and _HOST_CALL_RE.search(
                inst.attrs
            ):
                findings.append(
                    _finding(
                        "host-transfer",
                        "host-placement custom-call in compiled decode",
                        where,
                    )
                )
            elif _HOST_SPACE_RE.search(inst.result_type):
                findings.append(
                    _finding(
                        "host-transfer",
                        f"host memory space shape {inst.result_type}",
                        where,
                    )
                )
            if (
                inst.opcode in ("copy", "convert")
                and comp.name not in fusion_bodies
                and is_state(inst.result_type)
            ):
                copy_sites.append(f"{comp.name}{inst.name}[{inst.opcode}]")
    if copy_sites:
        findings.append(
            _finding(
                "state-buffer-copy",
                f"{len(copy_sites)} whole-state-buffer copy/convert op(s): "
                f"{', '.join(copy_sites[:4])}"
                f"{'...' if len(copy_sites) > 4 else ''} — the decode "
                f"program updates the buffer in place, so each is a copy "
                f"the backend added around an in-place store in a loop "
                f"(known on the CPU; the TPU program has none)",
                prog.label,
                severity="warning",
            )
        )

    # --- scan shape (block programs only)
    if prog.expect_trip is not None:
        from repro.launch.hlo_analysis import _trip_from_literals

        trips: list[int | None] = []
        for comp in comps.values():
            for inst in comp.instructions:
                if inst.opcode != "while":
                    continue
                m = _TRIP_RE.search(inst.attrs)
                if m:
                    trips.append(int(m.group(1)))
                    continue
                cond = re.search(r"condition=(%[\w.\-]+)", inst.attrs)
                trips.append(
                    _trip_from_literals(comps[cond.group(1)], comps)
                    if cond and cond.group(1) in comps
                    else None
                )
        if not trips:
            findings.append(
                _finding(
                    "scan-unrolled",
                    f"no while loop in the compiled block — XLA unrolled "
                    f"(rematerialized) the {prog.expect_trip}-wave scan "
                    f"body",
                    prog.label,
                )
            )
        elif prog.expect_trip not in [t for t in trips if t is not None]:
            known = sorted({t for t in trips if t is not None})
            if known:
                findings.append(
                    _finding(
                        "scan-trip-mismatch",
                        f"no while loop runs the expected {prog.expect_trip} "
                        f"waves (known trip counts: {known})",
                        prog.label,
                    )
                )
            else:
                findings.append(
                    _finding(
                        "scan-trip-unknown",
                        "while loop trip count is not statically known",
                        prog.label,
                        severity="warning",
                    )
                )
    return findings


# ------------------------------------------------------- lowering drivers


def lower_decode_programs(
    arch: str,
    *,
    n_slots: int = 2,
    max_len: int = 32,
    block: int | None = 8,
    greedy: bool = True,
) -> list[DecodeProgram]:
    """Lower+compile the decode step (and, with ``block``, the scan
    block) for ``arch``'s reduced config, shape-level: params come from
    ``jax.eval_shape`` and the state buffer is an aval — no weights, no
    cache, no device state is materialized. The program factories are
    the ones ``ResidentState`` jits, with the same donation, over the
    symmetric state."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_reduced
    from repro.core.unified import plan_state, state_records_from_pytree
    from repro.models.api import Model
    from repro.runtime.residency import (
        BLOCK_DONATE,
        DECODE_DONATE,
        StateResidency,
        block_impl,
        decode_impl,
    )
    from repro.runtime.sampling import SamplingParams, TokenSampler

    cfg = get_reduced(arch)
    model = Model.for_config(cfg)
    caches = jax.eval_shape(lambda: model.init_cache(n_slots, max_len))
    sp = plan_state(
        state_records_from_pytree(caches, n_slots=n_slots),
        n_slots=n_slots,
        max_len=max_len,
    )
    resid = StateResidency(sp, caches, n_slots=n_slots)
    params_aval = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    buf_aval = resid.aval
    tok_aval = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
    vec_i32 = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    vec_bool = jax.ShapeDtypeStruct((n_slots,), jnp.bool_)
    keys_aval = jax.ShapeDtypeStruct((n_slots, 2), jnp.uint32)
    eos_aval = jax.ShapeDtypeStruct((), jnp.int32)

    programs = [
        DecodeProgram(
            label=f"{arch}:step",
            hlo=jax.jit(
                decode_impl(model, resid),
                donate_argnums=DECODE_DONATE,
            )
            .lower(params_aval, tok_aval, buf_aval, vec_i32, vec_bool)
            .compile()
            .as_text(),
            state_type=hlo_type(buf_aval),
        )
    ]

    if block is not None:
        sampler = TokenSampler(
            SamplingParams(greedy=greedy), max_len=max_len
        )
        programs.append(
            DecodeProgram(
                label=f"{arch}:block{block}",
                hlo=jax.jit(
                    block_impl(model, resid, sampler, block),
                    donate_argnums=BLOCK_DONATE,
                )
                .lower(params_aval, buf_aval, tok_aval, vec_i32, vec_bool,
                       vec_bool, vec_i32, keys_aval, eos_aval)
                .compile()
                .as_text(),
                state_type=hlo_type(buf_aval),
                expect_trip=block,
            )
        )
    return programs


_BLOCK_ENTRY_RE = re.compile(r"(?:resident|paged)_block_(\d+)")


def lint_executables(bundle) -> list[Finding]:
    """Audit a v3 bundle's AOT executables AFTER deserialization: every
    entry must load, and the residency-backend entries must still carry
    the state-buffer donation aliasing (plus the host-transfer and scan
    checks of :func:`lint_program`) — proving serialization preserved
    the properties the publish gate certified on the live ``Compiled``.
    Presence/key-coherence checks that need no jax live in
    ``bundle_lint``; this pass loads executables, so it runs only where
    the pack's platform matches (the compile gate, same-platform
    audits)."""
    pack = getattr(bundle, "executables", None)
    if pack is None:
        return []
    from repro.runtime.aot import deserialize_compiled
    from repro.runtime.residency import state_buffer_aval

    findings: list[Finding] = []
    sp = bundle.state_plan
    # Paged buckets donate the *physical* pool buffer (null page + pool
    # pages), not the logical symmetric region — lint against that.
    state_type = hlo_type(state_buffer_aval(sp)) if sp is not None else ""
    for name, entry in sorted(pack.entries.items()):
        label = f"{bundle.arch}:{name}"
        try:
            hlo = deserialize_compiled(entry.payload).as_text()
        except Exception as e:
            findings.append(
                _finding(
                    "executable-load-failed",
                    f"AOT executable failed to deserialize on its own "
                    f"platform ({type(e).__name__}: {e})",
                    label,
                )
            )
            continue
        if not name.startswith(("resident_", "paged_")):
            continue  # pytree entries have no donated state buffer
        m = _BLOCK_ENTRY_RE.fullmatch(name)
        findings.extend(
            lint_program(
                DecodeProgram(
                    label=label,
                    hlo=hlo,
                    state_type=state_type,
                    expect_trip=int(m.group(1)) if m else None,
                )
            )
        )
    return findings


def lint_arch(
    arch: str,
    *,
    n_slots: int = 2,
    max_len: int = 32,
    block: int | None = 8,
    greedy: bool = True,
) -> Report:
    """Lower and lint every decode program for one architecture."""
    report = Report()
    for prog in lower_decode_programs(
        arch, n_slots=n_slots, max_len=max_len, block=block, greedy=greedy
    ):
        report.extend(lint_program(prog), checked=prog.label)
    return report


__all__ = [
    "DecodeProgram",
    "lint_arch",
    "lint_executables",
    "lint_program",
    "lower_decode_programs",
    "parse_alias_table",
]
