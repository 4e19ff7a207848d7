"""Small cells for CPU tests: the real configuration files' families and
the real traffic mixes, at sizes a test run can hold (six layers of
width 256, vocabulary 8192, four slots of 64 positions)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

QWEN3 = {
    "hidden_size": 256, "intermediate_size": 768, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "vocab_size": 8192,
}
QWEN3_PROGRAM = {"d_model": 256, "d_ff": 768, "n_periods": 6, "n_heads": 4,
                 "n_kv_heads": 2, "head_dim": 64, "vocab": 8192}
MAMBA2 = {"d_model": 256, "n_layer": 6, "vocab_size": 8000,
          "padded_vocab_size": 8192, "d_state": 32, "headdim": 32}
MAMBA2_PROGRAM = {"d_model": 256, "n_periods": 6, "vocab": 8192,
                  "ssm_state": 32, "ssm_head_dim": 32}


def config(name: str, dtype: str = "float32", n_slots: int = 4,
           max_len: int = 64) -> dict:
    """The real configuration file with its sizes cut down."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    family = cfg["reference"]
    cfg.update(QWEN3 if family == "qwen3" else MAMBA2)
    over = QWEN3_PROGRAM if family == "qwen3" else MAMBA2_PROGRAM
    cfg["program"] = {"arch": cfg["program"]["arch"],
                      "overrides": {**cfg["program"]["overrides"], **over,
                                    "dtype": dtype}}
    cfg["deployment"] = {"n_slots": n_slots, "max_len": max_len,
                         "dtype": dtype, "block_size": 1}
    return cfg


def mix(name: str, **arrivals) -> dict:
    """The real mix file with lengths cut to fit ``max_len`` 64."""
    m = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    m["prompt_tokens"] = {**m["prompt_tokens"], "median": 6, "min": 2, "max": 16}
    m["output_tokens"] = {**m["output_tokens"], "median": 8, "min": 2, "max": 24}
    m["arrivals"] = {**m["arrivals"], **arrivals}
    return m


def cell(config_name: str, mix_name: str, dtype: str = "float32",
         **arrivals) -> spec.Cell:
    return spec.Cell(f"tiny.{config_name}.{mix_name}", config(config_name, dtype),
                     mix(mix_name, **arrivals), 1, (), (), ROOT)
