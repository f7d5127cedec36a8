"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; each per-layer metric names itself. The harness finds:

* the configuration at the ``file`` its ``configs`` entry gives
  (``chipbench/configs/<config>.json``);
* the traffic mix at ``chipbench/traffic/<traffic>.json``, and its own
  generator hooks at ``chipbench/traffic/<traffic>.py`` where that file
  exists (``traffic.generator``);
* the cell's limits for ``correct`` at ``chipbench/limits/<cell>.json``;
* the reader of per-layer metric ``<name>`` at
  ``chipbench/layers/<name>.py`` (a module with ``read(ctx)``).

So a configuration, a mix or a metric is added as new files and new
entries here, with no file that is already there edited.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "chipbench" / "traffic" / f"{name}.json"


def limits(cell_name: str, root: pathlib.Path = ROOT) -> dict:
    path = root / "chipbench" / "limits" / f"{cell_name}.json"
    return json.loads(path.read_text())


def layer_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "chipbench" / "layers" / f"{name}.py"


def load_layer(name: str, root: pathlib.Path = ROOT):
    """The reader module of per-layer metric ``name``."""
    path = layer_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"{path}: a layer reader defines read(ctx)")
    return mod


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics a cell reports."""
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics a cell reports in a traced run: those that
    list it, and those without a list whose ``moves`` it reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
