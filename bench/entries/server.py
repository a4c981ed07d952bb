"""``server``: ``RetrievalServer``; a batch goes in through ``search``.

Every key of the configuration's ``index`` reaches the program: the
``EngineOpts`` fields (backend, precision, ...) as the server's engine
options, the rest (pivots, pairs, block) as index parameters.
"""

from __future__ import annotations

import dataclasses

import numpy as np


class Entry:
    """``RetrievalServer`` serving one metric."""

    def __init__(self, cfg: dict, corpus: np.ndarray):
        from repro.core.backends import EngineOpts
        from repro.serve.retrieval import RetrievalServer

        names = {f.name for f in dataclasses.fields(EngineOpts)}
        ix = cfg["index"]
        self.server = RetrievalServer(
            corpus, metric=cfg["metric"],
            opts=EngineOpts(**{k: v for k, v in ix.items() if k in names}),
            **{k: v for k, v in ix.items() if k not in names})

    def search(self, batch: np.ndarray, kind: str, **kw):
        return self.server.search(batch, kind, **kw)

    def warm(self, pool: np.ndarray, traffic: dict, kw: dict) -> str:
        """One call at the mix's batch size.  Returns the backend the
        engine resolved."""
        batch = pool[: int(traffic["batch"])]
        return self.search(batch, traffic["kind"], **kw).stats["backend"]

    def close(self) -> None:
        self.server = None
