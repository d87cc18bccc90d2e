"""Find a cell's configuration, traffic mix and metric readers by name.

Everything the harness runs is named in ``BENCHMARK.json`` and found in
files of its own, so a cell, a configuration, a mix or a metric is added
by adding files and an entry, never by editing one that is there:

- a configuration: the file the ``configs`` entry names (a JSON object
  with the deployment's settings) and the netlist it names beside it;
- a traffic mix: ``mixes/<traffic>.json``, read by ``traffic.py``;
- a metric: ``metrics/<name>.py`` with ``read(run) -> float | None``;
  a name ``base.part`` (``admit_ms.open``) falls back to
  ``metrics/base.py`` when it has no file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
REL = HERE.relative_to(ROOT)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    netlist: str          # the fabric in the paper's assembler syntax
    mix: dict
    end_to_end: list      # metric entries of BENCHMARK.json
    per_layer: list
    root: Path            # the checkout the cell was found in


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = root / entry["file"]
    config = json.loads(cfg_path.read_text())
    netlist = (cfg_path.parent / config["netlist"]).read_text()
    mix = json.loads((root / REL / "mixes" / f"{w['traffic']}.json")
                     .read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, netlist, mix, e2e, per_layer,
                root)


def reader(metric: str, root: Path = ROOT):
    """``read`` of the metric's own file under ``metrics/``."""
    d = Path(root) / REL / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
