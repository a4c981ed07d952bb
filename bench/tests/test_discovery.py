"""Everything a later cell brings is found by name, as new files: a
configuration, its data generator and metric, a traffic mix, its entry
point, the cell, and a per-layer metric.  No file the benchmark already has
is edited; only entries are added to BENCHMARK.json."""

from __future__ import annotations

import hashlib
import json
import shutil
import types
from pathlib import Path

import numpy as np

from bench import reference, run
from bench.cell import load_cell, named, reader

ROOT = Path(__file__).resolve().parents[2]
SERVER = (ROOT / "bench" / "entries" / "server.py").read_text()

GAUSS = '''
import numpy as np


def make(cfg, seed):
    x = np.random.default_rng([int(seed), 7]).standard_normal(
        (cfg["n_base"] + cfg["n_queries"], cfg["dim"]), dtype=np.float32)
    return x[:cfg["n_base"]], x[cfg["n_base"]:]
'''

# a metric the benchmark has no file for yet: l1, float64 by hand
L1 = '''
import numpy as np

OPS_PEAK = None
QUERY_BLOCK = 4
CONTROL_QUERY_BLOCK = 4


def ops(dim):
    return 0


def pair_elems(dim):
    return dim


def row_terms(c):
    return np.zeros(len(c))


def reference(q, c, _aux):
    return np.abs(q[:, None, :] - c[None, :, :]).sum(axis=-1)
'''


def _digests(tree: Path) -> dict:
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _add(tmp_path: Path) -> None:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = tmp_path / "bench"
    files = {
        "data/gauss.py": GAUSS,
        "distances/l1.py": L1,
        "entries/server-copy.py": SERVER,
        "configs/gauss-l2.json": json.dumps({
            "name": "gauss-l2", "metric": "l2", "dim": 16, "n_base": 3000,
            "n_queries": 200, "data": {"generator": "gauss",
                                       "structure_seed": 0},
            "index": {"n_pivots": 4, "n_pairs": 6, "block": 128,
                      "precision": "fp32", "backend": "jnp"},
            "reduced": []}),
        "traffic/knn-small.json": json.dumps({
            "entry": "server-copy", "loop": "closed", "kind": "knn",
            "batch": 16, "k": 5}),
        "workloads/gauss-l2.knn-small.json": json.dumps({
            "n_check": 16, "checks": {"knn_err": 1e-4, "unanswered": 0}}),
        "metrics/engine.calls.py": (
            "def read(ctx):\n"
            "    return len(ctx.rec['calls'])\n"),
    }
    for rel, text in files.items():
        (bench / rel).write_text(text)
    spec["configs"].append({
        "name": "gauss-l2", "source": "a test configuration",
        "file": "bench/configs/gauss-l2.json", "reduced": [],
        "why": "a test configuration"})
    spec["workloads"].append({
        "name": "gauss-l2.knn-small", "config": "gauss-l2",
        "traffic": "knn-small", "chips": 1, "why": "a test cell"})
    spec["per_layer"].append({
        "name": "engine.calls", "unit": "calls", "better": "lower",
        "source": "program_counter", "layer": "BSS engine",
        "moves": "qps", "workloads": ["gauss-l2.knn-small"]})
    # setup_s names no cells, so the new cell reports it with no entry here
    for m in spec["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("gauss-l2.knn-small")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_cell_is_found_by_name_and_runs(tmp_path):
    before = _digests(ROOT / "bench")
    _add(tmp_path)
    after = _digests(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    c = load_cell("gauss-l2.knn-small", tmp_path)
    assert c["data"].__file__.endswith("data/gauss.py")
    assert c["entry"].__file__.endswith("entries/server-copy.py")
    assert [m["name"] for m in c["per_layer"]] == ["engine.calls"]
    assert {m["name"] for m in c["end_to_end"]} == {"setup_s", "qps"}

    out = run.run_cell("gauss-l2.knn-small", 2**31 + 5, 0.3, False,
                       root=tmp_path, require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "qps"}
    # the cells already there are found as before
    old = load_cell("sift1m-l2.knn-batch", tmp_path)
    assert "engine.calls" not in [m["name"] for m in old["per_layer"]]
    ctx = types.SimpleNamespace(rec={"calls": [1, 2, 3]})
    assert reader(c, "engine.calls").read(ctx) == 3


def test_new_metric_reference_is_found_by_name(tmp_path):
    _add(tmp_path)
    l1 = named("distances", "l1", tmp_path)
    rng = np.random.default_rng(3)
    corpus, q = rng.random((50, 6)), rng.random((3, 6))
    ref = reference.Reference(l1, corpus)
    rows = {}
    ref.rows(q, lambda i, d: rows.__setitem__(i, d))
    want = np.abs(q[:, None, :] - corpus[None]).sum(axis=-1)
    np.testing.assert_allclose(np.stack([rows[i] for i in range(3)]), want)
