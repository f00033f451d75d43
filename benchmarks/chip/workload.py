"""Resolves a cell of ``BENCHMARK.json`` to its files, by name.

- configuration: the ``file`` its ``configs`` entry names; its ``model``
  key names ``models/<model>.py``, which builds the program and holds the
  plain reference and the FLOP counts;
- traffic: ``traffic/<traffic>.json``, read by ``traffic.py``;
- limits of the correctness check: ``workloads/<cell>.json``;
- every metric: ``metrics/<metric>.py``, whose ``read(run)`` returns its
  value or None where the run has nothing for it to read.

A new configuration, traffic mix, cell or metric is a new file and a new
entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    model: object
    end_to_end: dict  # metric name -> (entry, reader)
    per_layer: dict


def _readers(base: Path, entries: list, cell: str) -> dict:
    out = {}
    for m in entries:
        if cell in m.get("workloads", [cell]):
            out[m["name"]] = (m, load_module(base / "metrics" / f"{m['name']}.py", f"chipbench_metric_{m['name']}"))
    return out


def load(checkout: Path, name: str) -> Cell:
    import traffic

    base = Path(checkout) / HERE.relative_to(HERE.parents[1])
    bench = json.loads((Path(checkout) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((Path(checkout) / entry["file"]).read_text())
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=traffic.load(base / "traffic" / f"{w['traffic']}.json"),
        limits=json.loads((base / "workloads" / f"{name}.json").read_text())["limits"],
        model=load_module(base / "models" / f"{config['model']}.py", f"chipbench_model_{config['model']}"),
        end_to_end=_readers(base, bench["end_to_end"], name),
        per_layer=_readers(base, bench["per_layer"], name),
    )


def peak(kind: str) -> dict:
    """The published peaks of a chip of this ``device_kind``."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table or kind == "about":
        raise KeyError(f"device_kind {kind!r} is not in peaks.json; add its published peaks with their source")
    return table[kind]
