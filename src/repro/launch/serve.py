"""Serving launcher: embed a corpus with the two-tower model, build the
supermetric index, serve batched retrieval queries.

    PYTHONPATH=src python -m repro.launch.serve --corpus 20000 --queries 256 --k 10
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.queue import now
from repro.serve.retrieval import RetrievalServer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", type=int, default=20_000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--min-score", type=float, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    bundle = get_arch("two-tower-retrieval")
    model, cfg, _ = bundle.make_reduced()
    params = model.init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    item_ids = rng.integers(0, cfg.vocab, size=(args.corpus, cfg.n_item_fields))
    user_ids = rng.integers(0, cfg.vocab, size=(args.queries, cfg.n_user_fields))

    print(f"embedding corpus of {args.corpus} items ...")
    corpus = np.asarray(model.item_embed(params, item_ids))
    users = np.asarray(model.user_embed(params, user_ids))

    t0 = now()
    server = RetrievalServer(corpus)
    print(f"built supermetric index in {now() - t0:.2f}s "
          f"({server.index.n_blocks} blocks)")

    if args.min_score is not None:
        hits = server.range_query(users, args.min_score)
        sizes = [len(h) for h in hits]
        print(f"range query >= {args.min_score}: mean {np.mean(sizes):.1f} hits")
    else:
        t0 = now()
        top = server.top_k(users, args.k)
        dt = now() - t0
        print(f"top-{args.k} for {args.queries} queries in {dt:.2f}s")
    s = server.stats
    print(f"distances/query: {s.dists_per_query:.0f} "
          f"(exhaustive would be {args.corpus}) -> {100 * s.saving:.1f}% pruned")


if __name__ == "__main__":
    main()
