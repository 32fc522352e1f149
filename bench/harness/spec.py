"""Finds every piece of a cell by the names in ``BENCHMARK.json``.

* configuration ``<c>``: its sizes in the entry's ``file``
  (``bench/configs/<c>.json``), and in ``bench/configs/<c>.py`` its
  ``build(cfg, seed)``, which holds the stack to the stated precision, its
  ``check(stack, served, batches, sample, control=False)`` against its
  plain references, and the ``LIMITS`` that check applies;
* traffic mix ``<m>``: ``bench/traffic/<m>.json``;
* metric ``<p>``, end-to-end or per-layer: a reader
  ``bench/metrics/<p>.py`` defining ``read(ctx)``, which returns a number
  or None when it finds nothing.

Adding a cell adds files and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from typing import List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _one(entries: List[dict], name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        known = ", ".join(e["name"] for e in entries)
        raise KeyError(f"{what} {name!r} not found once in BENCHMARK.json (known: {known})")
    return found[0]


def workload(bench: dict, name: str) -> dict:
    return _one(bench["workloads"], name, "workload")


def config_entry(bench: dict, name: str) -> dict:
    return _one(bench["configs"], name, "configuration")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / config_entry(bench, name)["file"]).read_text())


def _load_module(path: pathlib.Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    mod_name = "bench_" + tag + "_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(name: str, root: pathlib.Path = ROOT):
    return _load_module(root / "bench" / "configs" / f"{name}.py", "config")


def load_traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str, root: pathlib.Path = ROOT):
    mod = _load_module(root / "bench" / "metrics" / f"{name}.py", "metric")
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"bench/metrics/{name}.py defines no read(ctx)")
    return mod.read


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> List[dict]:
    """Per-layer metrics a traced run of ``cell`` reports: those listing
    it, and those without a list whose end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)]
