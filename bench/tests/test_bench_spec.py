"""A new configuration, traffic mix and metric are found by name from
new files and new entries, with no edit to a file that exists."""

import json
import shutil
import sys

import pytest

import tiny  # noqa: F401
from harness import counts, spec


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench_json = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    # new files only
    cfg = json.loads((tmp_path / "bench/configs/qwen3-0.6b.json").read_text())
    cfg["name"] = "qwen3-new"
    (tmp_path / "bench/configs/qwen3-new.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "bench/traffic/chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 0.123
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    # a family the harness has never seen: its reference and its counts
    toy = dict(cfg, name="toy-1b", reference="toy", width=512)
    (tmp_path / "bench/configs/toy-1b.json").write_text(json.dumps(toy))
    (tmp_path / "bench/reference/toy.py").write_text(
        "def forward(params, tokens, config, mm=None):\n"
        "    return tokens\n\n\n"
        "def unembed(params):\n    return params\n")
    (tmp_path / "bench/counts/toy.py").write_text(
        "from harness import counts\n\n\n"
        "def dims(config):\n"
        "    w = config['width']\n"
        "    return counts.Dims(d=w, layers=2, vocab=1000, item=2,\n"
        "                       layer_matrix_params=w * w,\n"
        "                       layer_vector_params=w, layer_flops_fixed=7.0,\n"
        "                       layer_flops_per_position=3.0,\n"
        "                       state_bytes_per_token=11, fixed_state_bytes=5)\n")
    # new entries only
    bench_json["configs"].append(dict(bench_json["configs"][0],
                                      name="qwen3-new",
                                      file="bench/configs/qwen3-new.json"))
    bench_json["configs"].append(dict(bench_json["configs"][0],
                                      name="toy-1b",
                                      file="bench/configs/toy-1b.json"))
    bench_json["workloads"].append({"name": "toy-1b.new-mix",
                                    "config": "toy-1b", "traffic": "new-mix",
                                    "chips": 1, "why": "test"})
    bench_json["workloads"].append({"name": "qwen3-new.new-mix",
                                    "config": "qwen3-new",
                                    "traffic": "new-mix", "chips": 1,
                                    "why": "test"})
    bench_json["per_layer"].append({"name": "new_metric", "unit": "ms",
                                    "better": "lower", "source": "host_clock",
                                    "layer": "device", "moves": "tokens_per_s",
                                    "workloads": ["qwen3-new.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))

    cell = spec.load_cell("qwen3-new.new-mix", tmp_path)
    assert cell.config["name"] == "qwen3-new"
    assert cell.mix["arrivals"]["rate_per_s"] == 0.123
    names = [m["name"] for m in cell.metrics(trace=True)]
    assert "new_metric" in names
    assert spec.metric_reader(tmp_path, "new_metric")(None) == 42.0
    # the metric listed for the new cell only is absent elsewhere
    old = spec.load_cell("qwen3-0.6b.decode-batch", tmp_path)
    assert "new_metric" not in [m["name"] for m in old.metrics(trace=True)]
    assert spec.reference_module(tmp_path, cell.config).forward
    # the new family's reference and counts are found from its files
    toy_cell = spec.load_cell("toy-1b.new-mix", tmp_path)
    assert spec.reference_module(tmp_path, toy_cell.config).forward(
        None, "tokens", toy_cell.config) == "tokens"
    m = spec.dims(tmp_path, toy_cell.config)
    assert m.d == 512
    # two layers of 2*512*512 + 7 + 3*10 operations, and the head
    assert counts.token_flops(m, 10, True) == (2 * (2 * 512 * 512 + 7 + 30)
                                               + 2 * 512 * 1000)
    assert counts.live_state_bytes(m, 10) == 10 * 11 + 5


def test_cells_of_the_benchmark_resolve():
    bench_json = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"])
        for m in cell.metrics(False) + cell.metrics(True):
            assert callable(spec.metric_reader(tiny.ROOT, m["name"]))
        assert any(m["name"] == "setup_s" for m in cell.metrics(False))


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    assert "bench" in sys.modules["harness.spec"].__file__
