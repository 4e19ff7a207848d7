"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own, found from the names in
``BENCHMARK.json``:

* ``configs[].file``                 the configuration (sizes, deployment);
* ``bench/traffic/<traffic>.json``   the traffic mix (parameters only);
* ``bench/metrics/<metric>.py``      one reader per metric;
* ``bench/reference/<family>.py``    the plain float32 forward pass, and
* ``bench/counts/<family>.py``       the family's sizes for the operation
  and byte counts, both named by the configuration's ``reference`` key.

A new cell therefore needs new files and new entries, and no edit to a
file that exists.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """A cell, file or metric named in BENCHMARK.json cannot be found."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    root: Path

    def metrics(self, trace: bool) -> tuple[dict, ...]:
        """The metrics this cell reports: its end-to-end ones untraced,
        its per-layer ones traced."""
        return self.per_layer if trace else self.end_to_end


def _reports(metric: dict, workload: str) -> bool:
    listed = metric.get("workloads")
    return listed is None or workload in listed


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """Resolve ``workload`` against ``root/BENCHMARK.json``."""
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise SpecError(f"no BENCHMARK.json under {root}")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    return make_cell(workload, cell["config"], cell["traffic"],
                     int(cell["chips"]), root, spec)


def make_cell(workload: str, config_name: str, traffic: str, chips: int,
              root: Path = ROOT, spec: dict | None = None) -> Cell:
    """A cell of ``config_name`` under the mix ``traffic``, reporting the
    metrics that ``BENCHMARK.json`` gives ``workload``."""
    if spec is None:
        spec = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    if config_name not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{config_name!r}")
    config = json.loads((root / configs[config_name]["file"]).read_text())
    mix_path = root / "bench" / "traffic" / f"{traffic}.json"
    if not mix_path.is_file():
        raise SpecError(f"no traffic mix file {mix_path}")
    mix = json.loads(mix_path.read_text())
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, workload))
    layer = tuple(m for m in spec["per_layer"] if _reports(m, workload))
    return Cell(workload, config, mix, chips, e2e, layer, root)


@functools.lru_cache(maxsize=None)
def load_module(path: Path, name: str) -> ModuleType:
    """Import one file by path, once (metric readers and references are
    files found by name, not a package)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(root: Path, name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    module = load_module(root / "bench" / "metrics" / f"{name}.py",
                         f"bench_metric_{name.replace('.', '_')}")
    return module.read


def reference_module(root: Path, config: dict) -> ModuleType:
    family = config["reference"]
    return load_module(root / "bench" / "reference" / f"{family}.py",
                       f"bench_reference_{family}")


def dims(root: Path, config: dict):
    """The configuration's sizes for ``bench/harness/counts.py``, from
    ``bench/counts/<family>.py``."""
    family = config["reference"]
    return load_module(root / "bench" / "counts" / f"{family}.py",
                       f"bench_counts_{family}").dims(config)


def peaks(root: Path, device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
