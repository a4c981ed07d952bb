"""Everything that defines a cell, found by name.

``BENCHMARK.json`` names a cell's configuration, traffic mix and metrics.
Each piece lives in a file of its own under ``bench/``:

* ``configs/<config>.json``: sizes, metric, index parameters.  Its
  ``data.generator`` names ``data/<generator>.py`` (corpus and query pool
  from the seed) and its ``metric`` names ``distances/<metric>.py`` (the
  float64 reference, the control and the roofline's work count);
* ``traffic/<mix>.json``: entry point, loop and the mix's parameters.  Its
  ``entry`` names ``entries/<entry>.py`` and its ``loop``
  ``traffic/<loop>.py``;
* ``workloads/<cell>.json``: the cell's own numbers (rate, radius, the
  limits of ``correct``);
* ``metrics/<metric>.py``: one reader per metric.

A new cell, mix, configuration, data generator, entry point, distance or
metric is a new file; no existing file changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no bench/{kind}/{name}.py")
    return load_module(path)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything that defines cell ``name``, found by name from
    ``root/BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / "bench"
    (work,) = [w for w in spec["workloads"] if w["name"] == name] or [None]
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    (conf,) = [c for c in spec["configs"] if c["name"] == work["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{work['traffic']}.json")
                         .read_text())

    def applies(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name,
        "chips": int(work["chips"]),
        "config": cfg,
        "traffic": traffic,
        "cell": json.loads((bench / "workloads" / f"{name}.json").read_text()),
        "loop": named("traffic", traffic["loop"], root),
        "entry": named("entries", traffic["entry"], root),
        "data": named("data", cfg["data"]["generator"], root),
        "distance": named("distances", cfg["metric"], root),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "root": root,
    }


def reader(c: dict, metric: str):
    """The reader of ``metric`` (``bench/metrics/<metric>.py``)."""
    return named("metrics", metric, c["root"])


def request_params(traffic: dict, cell: dict) -> dict:
    """The keyword parameters of one request of the mix's kind: the cell's
    radius for range, the mix's k for kNN."""
    if traffic["kind"] == "range":
        return {"t": float(cell["radius"])}
    return {"k": int(traffic["k"])}
