"""``front``: ``build_bss`` then ``ServingFront`` with the front's defaults.
Requests go in through ``submit`` and come back through their futures.

Every key of the configuration's ``index`` reaches the program: the
``EngineOpts`` fields (backend, precision, ...) as the front's engine
options, the rest (pivots, pairs, block) as ``build_bss`` parameters.
"""

from __future__ import annotations

import dataclasses

import numpy as np


class Entry:
    """``ServingFront`` over a ``build_bss`` index."""

    def __init__(self, cfg: dict, corpus: np.ndarray):
        from repro.core import flat_index
        from repro.core.backends import EngineOpts
        from repro.serve.front import ServingFront

        names = {f.name for f in dataclasses.fields(EngineOpts)}
        ix = cfg["index"]
        # the front's own default realisation is "dense" (its bounded
        # recompiles); explicit options replace that default, so it is kept
        # unless the configuration states one
        opts = EngineOpts(**{"realisation": "dense",
                             **{k: v for k, v in ix.items() if k in names}})
        self._flat_index = flat_index
        self.index = flat_index.build_bss(
            cfg["metric"], corpus,
            **{k: v for k, v in ix.items() if k not in names})
        self.front = ServingFront(self.index, opts=opts)

    def warm(self, pool: np.ndarray, traffic: dict, kw: dict) -> str:
        """Compile every bucket of the front's ladder as the front
        dispatches it: float32 rows, and for range per-query radii with
        the last row at the ``-1`` padding radius.  Returns the backend the
        engine resolved."""
        kind = traffic["kind"]
        backend = ""
        for b in self.front.buckets:
            if kind == "range":
                t = np.full(b, kw["t"], np.float32)
                t[-1] = -1.0
                _, stats = self._flat_index.bss_query_batched(
                    self.index, pool[:b], t, opts=self.front.opts)
            else:
                _, _, stats = self._flat_index.bss_knn_batched(
                    self.index, pool[:b], kw["k"], opts=self.front.opts)
            backend = stats["backend"]
        self.front.submit(pool[0], kind, **kw).result()
        return backend

    def submit(self, query: np.ndarray, kind: str, **kw):
        return self.front.submit(query, kind, **kw)

    def close(self) -> None:
        self.front.close()
