"""Finds everything a cell needs by its name in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own, found by name:

- ``chipbench/configs/<config>.json``: the model as run, its source, the
  keys reduced from it, its plain reference (``references/<ref>.py``);
- ``chipbench/traffic/<mix>.json``: the mix's parameters
  (``traffic.py`` reads them);
- ``chipbench/cells/<workload>.json``: how the cell serves the mix
  (slots, chunk, page size, memory share, load) and the limits of its
  correctness numbers;
- ``chipbench/metrics/<metric>.py``: one reader per metric, with a
  ``read(run)`` that returns a number or None.

Adding a configuration, a mix, a cell or a metric adds files and
``BENCHMARK.json`` entries; no file here changes.  An unknown name is an
error, never a default.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent


class UnknownName(KeyError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict       # the configuration file as written
    model: dict        # published keys as run, with the assumed ones
    traffic_name: str
    traffic: dict
    settings: dict     # cells/<workload>.json
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    root: pathlib.Path = HERE  # where its files were found


def load_benchmark(path: Optional[pathlib.Path] = None) -> dict:
    path = path or REPO / "BENCHMARK.json"
    return json.loads(path.read_text())


def _file(root: pathlib.Path, kind: str, name: str, ext: str) -> pathlib.Path:
    p = root / kind / f"{name}{ext}"
    if not p.is_file():
        raise UnknownName(f"no {kind[:-1] if kind.endswith('s') else kind} "
                          f"file for {name!r} ({p})")
    return p


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"unknown {what} {name!r}; known: "
                      f"{sorted(e['name'] for e in entries)}")


def model_of(config: dict) -> dict:
    """The model's keys as run: the published keys at the top level, with
    the sizes the source leaves to convention (``assumed``) merged in."""
    skip = {"name", "source", "reduced", "assumed", "deployment", "program",
            "reference", "numerics", "notes"}
    out = {k: v for k, v in config.items() if k not in skip}
    out.update(config.get("assumed", {}))
    return out


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              root: pathlib.Path = HERE) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    w = _by_name(bench["workloads"], name, "workload")
    _by_name(bench["configs"], w["config"], "configuration")
    config = json.loads(_file(root, "configs", w["config"], ".json")
                        .read_text())
    traffic = json.loads(_file(root, "traffic", w["traffic"], ".json")
                         .read_text())
    settings = json.loads(_file(root, "cells", name, ".json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    for m in e2e + layer:
        _file(root, "metrics", m["name"], ".py")
    _file(root, "references", config["reference"], ".py")
    return Cell(name=name, config_name=w["config"], config=config,
                model=model_of(config), traffic_name=w["traffic"],
                traffic=traffic, settings=settings, chips=int(w["chips"]),
                end_to_end=e2e, per_layer=layer, root=root)


@functools.lru_cache(maxsize=None)
def _load_module(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = HERE) -> Callable:
    mod = _load_module(_file(root, "metrics", name, ".py"),
                       f"chipbench_metric_{name.replace('.', '_')}")
    return mod.read


def reference(name: str, root: pathlib.Path = HERE):
    return _load_module(_file(root, "references", name, ".py"),
                        f"chipbench_reference_{name}")


def readers(metrics: List[dict], root: pathlib.Path = HERE
            ) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in metrics}
