"""Find a cell's configuration, traffic mix, metrics and peaks by the names
in ``BENCHMARK.json``.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, found by name:

  bench/configs/<config>.json   (the ``file`` of the config's entry)
  <its "reference">             the module of everything family-specific:
                                ``program_config``, the weights, the plain
                                reference's logits, the FLOP and byte counts
                                (``bench/reference.py`` for dense decoders)
  bench/traffic/<traffic>.json  parameters read by ``bench.traffic.generate``
  bench/metrics/<metric>.py     a reader ``read(run) -> float | None``
  bench/peaks.json              per ``device_kind``

so a later cell, mix or metric is added by adding files.  An unknown name
is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict            # the configuration file, as run
    reference: ModuleType   # the module the configuration names
    traffic_name: str
    traffic: Dict           # the traffic file
    end_to_end: List[Dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]


def load_benchmark(root: Path = ROOT) -> Dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(sorted(e["name"] for e in entries)) or "none"
    raise KeyError(f"unknown {what} {name!r} (known: {known})")


def end_to_end_for(bench: Dict, cell: str) -> List[Dict]:
    """The end-to-end metrics ``cell`` reports: those without a
    ``workloads`` key, and those that list it."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: Dict, cell: str) -> List[Dict]:
    """The per-layer metrics ``cell`` reports: those that list it, and
    those without ``workloads`` whose ``moves`` metric the cell reports."""
    reported = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in reported)]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = json.loads((root / c["file"]).read_text())
    traffic_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise FileNotFoundError(f"no traffic file {traffic_path}")
    return Cell(name=name, chips=int(w["chips"]), config_name=c["name"],
                config=config, reference=load_reference(config, root),
                traffic_name=w["traffic"],
                traffic=json.loads(traffic_path.read_text()),
                end_to_end=end_to_end_for(bench, name),
                per_layer=per_layer_for(bench, name))


def _exec_file(path: Path, prefix: str, name: str) -> ModuleType:
    mod_name = prefix + "".join(ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    return _exec_file(path, "bench_metric_", metric).read


@functools.lru_cache(maxsize=None)
def _reference_at(path: Path) -> ModuleType:
    # one module object per file, so its compiled programs are reused
    return _exec_file(path, "bench_reference_", str(path))


def load_reference(conf: Dict, root: Path = ROOT) -> ModuleType:
    """The module a configuration names under ``reference``, a path
    relative to the root."""
    if "reference" not in conf:
        raise KeyError(f"configuration {conf.get('registry_id')!r} names no "
                       f"reference module")
    path = (Path(root) / conf["reference"]).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no reference module at {path}")
    return _reference_at(path)


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
