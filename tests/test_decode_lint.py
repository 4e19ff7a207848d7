"""Static lint of compiled decode programs (repro.analysis.decode_lint).

Synthetic-HLO cases pin each finding code's trigger; the real-lowering
case proves the ISSUE acceptance invariant for a CI serving arch —
donation actually aliases the state buffer and the hot path contains no
device→host transfer — without running a single decode step.
"""

import pytest

from repro.analysis import decode_lint
from repro.analysis.decode_lint import DecodeProgram, parse_alias_table

STATE = "u8[1024]"  # synthetic state-buffer type


def _module(body: str, *, alias: str = "{ {1}: (2, {}, may-alias) }") -> str:
    alias_attr = f", input_output_alias={alias}" if alias else ""
    return (
        f"HloModule jit_step, is_scheduled=true{alias_attr}\n"
        "\n"
        "ENTRY %main.10 (p0: f32[4], p1: s32[2,1], p2: u8[1024]) -> (f32[4], u8[1024]) {\n"
        "  %p0 = f32[4]{0} parameter(0)\n"
        "  %p1 = s32[2,1]{1,0} parameter(1)\n"
        "  %p2 = u8[1024]{0} parameter(2)\n"
        f"{body}"
        "  ROOT %tuple.1 = (f32[4]{0}, u8[1024]{0}) tuple(%p0, %p2)\n"
        "}\n"
    )


def _codes(findings):
    return {f.code for f in findings}


def test_parse_alias_table():
    hlo = _module("")
    assert parse_alias_table(hlo) == [((1,), 2, "may-alias")]
    multi = _module(
        "", alias="{ {0}: (0, {}, must-alias), {1, 0}: (2, {}, may-alias) }"
    )
    assert parse_alias_table(multi) == [
        ((0,), 0, "must-alias"),
        ((1, 0), 2, "may-alias"),
    ]
    assert parse_alias_table("HloModule bare\n") == []


def test_clean_program_passes():
    prog = DecodeProgram(label="t:step", hlo=_module(""), state_type=STATE)
    assert decode_lint.lint_program(prog) == []


def test_state_not_donated():
    prog = DecodeProgram(
        label="t:step", hlo=_module("", alias=""), state_type=STATE
    )
    assert _codes(decode_lint.lint_program(prog)) == {"state-not-donated"}


def test_state_param_missing():
    prog = DecodeProgram(
        label="t:step", hlo=_module(""), state_type="u8[1025]"
    )
    assert "state-param-missing" in _codes(decode_lint.lint_program(prog))


def test_host_transfer_codes():
    prog = DecodeProgram(
        label="t:step",
        hlo=_module(
            "  %tok = token[] after-all()\n"
            "  %of = token[] outfeed(%p0, %tok), outfeed_shape=f32[4]\n"
        ),
        state_type=STATE,
    )
    assert "host-transfer" in _codes(decode_lint.lint_program(prog))

    prog = DecodeProgram(
        label="t:step",
        hlo=_module(
            '  %cc = f32[4]{0} custom-call(%p0), custom_call_target="MoveToHost"\n'
        ),
        state_type=STATE,
    )
    assert "host-transfer" in _codes(decode_lint.lint_program(prog))

    prog = DecodeProgram(
        label="t:step",
        hlo=_module("  %h = f32[4]{0:S(5)} copy(%p0)\n"),
        state_type=STATE,
    )
    assert "host-transfer" in _codes(decode_lint.lint_program(prog))


def test_whole_buffer_copy_is_warning_and_fusion_internal_is_exempt():
    prog = DecodeProgram(
        label="t:step",
        hlo=_module("  %cp = u8[1024]{0} copy(%p2)\n"),
        state_type=STATE,
    )
    findings = decode_lint.lint_program(prog)
    assert _codes(findings) == {"state-buffer-copy"}
    assert all(f.severity == "warning" for f in findings)

    # the same copy inside a fusion body stays in registers: exempt
    fused = (
        "HloModule jit_step, is_scheduled=true, "
        "input_output_alias={ {1}: (2, {}, may-alias) }\n"
        "\n"
        "%fused_computation (fp: u8[1024]) -> u8[1024] {\n"
        "  %fp = u8[1024]{0} parameter(0)\n"
        "  ROOT %cp = u8[1024]{0} copy(%fp)\n"
        "}\n"
        "\n"
        "ENTRY %main.10 (p0: f32[4], p1: s32[2,1], p2: u8[1024]) -> (f32[4], u8[1024]) {\n"
        "  %p0 = f32[4]{0} parameter(0)\n"
        "  %p1 = s32[2,1]{1,0} parameter(1)\n"
        "  %p2 = u8[1024]{0} parameter(2)\n"
        "  %fu = u8[1024]{0} fusion(%p2), kind=kLoop, calls=%fused_computation\n"
        "  ROOT %tuple.1 = (f32[4]{0}, u8[1024]{0}) tuple(%p0, %fu)\n"
        "}\n"
    )
    prog = DecodeProgram(label="t:step", hlo=fused, state_type=STATE)
    assert decode_lint.lint_program(prog) == []


def _while_module(*, trip_attr: str) -> str:
    return (
        "HloModule jit_block, input_output_alias={ {0}: (0, {}, may-alias) }\n"
        "\n"
        "%cond (cp: u8[1024]) -> pred[] {\n"
        "  %cp = u8[1024]{0} parameter(0)\n"
        "  ROOT %lt = pred[] constant(false)\n"
        "}\n"
        "\n"
        "%body (bp: u8[1024]) -> u8[1024] {\n"
        "  ROOT %bp = u8[1024]{0} parameter(0)\n"
        "}\n"
        "\n"
        "ENTRY %main.20 (p0: u8[1024]) -> u8[1024] {\n"
        "  %p0 = u8[1024]{0} parameter(0)\n"
        "  ROOT %w = u8[1024]{0} while(%p0), condition=%cond, body=%body"
        f"{trip_attr}\n"
        "}\n"
    )


def test_scan_shape_codes():
    good = DecodeProgram(
        label="t:block4",
        hlo=_while_module(
            trip_attr=', backend_config={"known_trip_count":{"n":"4"}}'
        ),
        state_type=STATE,
        expect_trip=4,
    )
    assert decode_lint.lint_program(good) == []

    mismatch = DecodeProgram(
        label="t:block4",
        hlo=_while_module(
            trip_attr=', backend_config={"known_trip_count":{"n":"8"}}'
        ),
        state_type=STATE,
        expect_trip=4,
    )
    assert "scan-trip-mismatch" in _codes(decode_lint.lint_program(mismatch))

    unknown = DecodeProgram(
        label="t:block4",
        hlo=_while_module(trip_attr=""),
        state_type=STATE,
        expect_trip=4,
    )
    f = decode_lint.lint_program(unknown)
    assert "scan-trip-unknown" in _codes(f)
    assert all(x.severity == "warning" for x in f)

    unrolled = DecodeProgram(
        label="t:block4",
        hlo=_module(""),
        state_type=STATE,
        expect_trip=4,
    )
    assert "scan-unrolled" in _codes(decode_lint.lint_program(unrolled))


def test_unparseable_hlo():
    prog = DecodeProgram(label="t:step", hlo="not hlo", state_type=STATE)
    assert _codes(decode_lint.lint_program(prog)) == {"hlo-unparseable"}


# --------------------------------------------------------- real lowering


def test_real_decode_programs_pass_lint():
    """ISSUE acceptance, statically: the compiled decode step and scan
    block of a CI serving arch have their state-buffer donation aliased
    and zero host transfers. (scripts/ci.sh runs this for every CI arch;
    one arch here keeps the suite fast.)"""
    pytest.importorskip("jax")
    programs = decode_lint.lower_decode_programs(
        "qwen3-0.6b", n_slots=2, max_len=16, block=4
    )
    assert {p.label for p in programs} == {
        "qwen3-0.6b:step", "qwen3-0.6b:block4"
    }
    for prog in programs:
        # donation must be visible in the alias table before linting
        assert parse_alias_table(prog.hlo), prog.label
        findings = decode_lint.lint_program(prog)
        errors = [f for f in findings if f.severity == "error"]
        assert not errors, [f.render() for f in errors]
        assert not any(f.code == "host-transfer" for f in findings)
