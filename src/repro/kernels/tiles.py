"""Tile-size knobs for the masked Pallas kernel family.

The fused engines tile their work as (query-tile x corpus-block) cells with
a K-lane chunk bounding the VPU broadcast transient.  The defaults
(128 / 128 / 128) match the MXU systolic array, the BSS block size and the
TPU's 128-lane vector width — every block meets the (8, 128) rule the TPU
compiler enforces, and a K chunk narrower than 128 lanes is padded to 128
anyway (a 64-lane chunk at K=128 ran out of scoped VMEM in a v5e compile);
but
real-TPU autotuning (see ROADMAP "Pallas masked-kernel autotuning") needs a
way to try other shapes WITHOUT a rebuild — so each constant reads an
environment variable at import time:

    REPRO_TILE_BQ      query-tile rows   (kernel bm / bq)      default 128
    REPRO_TILE_BLOCK   corpus-block cols (kernel bn / bb)      default 128
    REPRO_TILE_KCHUNK  K lanes reduced per VPU pass            default 128
    REPRO_TILE_VPU     standalone VPU-kernel tile (bm = bn)    default 128

This module is import-light on purpose (no jax): it must be readable by
tooling/subprocesses without paying the jax import.  Consumers:
``kernels/pairwise_dist.py`` (bm/bn), ``kernels/planar_exclusion.py``
(bq/bb), ``kernels/jsd_dist.py`` / ``kernels/tri_dist.py`` (K-chunk),
``core/flat_index.py`` and ``forest/walk.py`` (query-tile default).
"""

from __future__ import annotations

import os

__all__ = ["TILE_BQ", "TILE_BLOCK", "TILE_KCHUNK", "TILE_VPU"]


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = int(raw)
    except ValueError as e:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from e
    if val <= 0:
        raise ValueError(f"{name} must be positive, got {val}")
    return val


# query-tile rows of every masked/unmasked pairwise kernel (bm / bq)
TILE_BQ = _env_int("REPRO_TILE_BQ", 128)

# corpus-block columns (bn / bb); the BSS index build keeps its own `block`
# parameter — for "block pruned == grid cell skipped" they should agree
TILE_BLOCK = _env_int("REPRO_TILE_BLOCK", 128)

# K lanes reduced per VPU pass in the broadcast-reduction tile kernels
# (jsd / triangular); bounds the (bm, bn, Kc) VMEM transient
TILE_KCHUNK = _env_int("REPRO_TILE_KCHUNK", 128)

# default square tile of the STANDALONE VPU kernels (the unmasked
# jsd/triangular entry points); 128 because the TPU refuses a 64-wide
# output block (last dim must divide by 128); the BSS masked exact phase
# always uses bm=TILE_BQ / bn=TILE_BLOCK
TILE_VPU = _env_int("REPRO_TILE_VPU", 128)
