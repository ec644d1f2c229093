"""Find the files a cell is made of, by the names BENCHMARK.json gives.

Everything that belongs to one cell, configuration, traffic mix, generator
or per-layer metric sits in a file of its own under ``benchmark/``; a later
PR adds files and entries and edits none. A name with no file is an error
that names the path looked for.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class UnknownName(LookupError):
    """A cell, configuration, mix, generator, metric or reader with no file."""


def _path(root: str, kind: str, name: str, ext: str) -> str:
    if not NAME.match(name):
        raise UnknownName(f"{kind[:-1]} name {name!r} is not a name "
                          f"(letters, digits, '_', '.', '-')")
    path = os.path.join(root, kind, name + ext)
    if not os.path.isfile(path):
        raise UnknownName(f"no {kind[:-1]} {name!r}: looked for {path}")
    return path


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    """``<root>/<kind>/<name>.json`` as a dict."""
    with open(_path(root, kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """``<root>/<kind>/<name>.py`` as a module (runners, generators,
    readers): loaded from its path, so a copy of the data directories
    elsewhere works the same."""
    path = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """A cell with everything it names resolved: its configuration, its
    traffic mix (the cell's own ``traffic_params`` laid over the mix's)
    and its per-layer metric files."""
    cell = load_json("workloads", name, root)
    cell["name"] = name
    cell["config_file"] = load_json("configs", cell["config"], root)
    mix = load_json("traffic", cell["traffic"], root)
    cell["mix"] = {**mix, **cell.get("traffic_params", {})}
    cell["layer_metric_files"] = [load_json("layer_metrics", m, root)
                                  for m in cell["per_layer"]]
    # fail now, not after the window, if a generator/runner/reader is missing
    _path(root, "traffic", cell["mix"]["generator"], ".py")
    _path(root, "runners", cell["runner"], ".py")
    for m in cell["layer_metric_files"]:
        _path(root, "readers", m["reader"], ".py")
    return cell
