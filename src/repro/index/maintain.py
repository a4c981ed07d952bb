"""Incremental maintenance of the blocked BSS index: a living corpus.

``build_bss`` is a batch build; this module keeps a built index serving
while the corpus changes, without rebuilding it:

* :func:`append` packs new rows into FRESH blocks against the EXISTING
  pivot / pivot-pair reference tables.  The paper's blocked layout (§6:
  per-block reference tables over fixed-size blocks) is naturally
  append-friendly — a new block needs only its OWN planar tables, so the
  host-side table work is exactly ``m × P`` pivot distances for ``m`` new
  rows (recorded in the mutation stats; never the ``n × P`` of a rebuild).
  New rows get their own locality permutation (the same recursive
  median-split ``build_bss`` uses, run over the new rows only), existing
  blocks' data and boxes are untouched, and the device mirrors are
  EXTENDED: a live single-device mirror grows by suffix-concatenation (only
  the new blocks cross host→device); a live sharded mirror consumes its
  empty PADDING blocks first — those sit at the end of the padded layout,
  i.e. on the least-loaded shard — via a sharding-preserving device-side
  splice that changes no array shape, so the cached shard_map callables
  keep serving with ZERO recompiles (``ShardedBSSIndex.extended``).  Only
  when the new blocks outgrow the padding does the sharded mirror fall back
  to a lazy re-layout.

* :func:`delete` tombstones rows through the per-block valid counts every
  engine already honours: the slot's ``valid`` bit clears (the masked exact
  phases and the distance accounting read it) and its ``perm`` entry
  becomes -1 (the padding sentinel the oracle and hit extraction already
  skip).  Block boxes are left alone — a box over a superset of the live
  points only ever LOOSENS the lower bound, which is sound (never excludes
  a true hit); compaction re-tightens.

* :func:`compact` re-permutes the live rows into a fresh layout when
  tombstones or append-growth have degraded it: with ``refresh_pivots=True``
  it reruns the full build (FFT pivot selection included) over the live
  rows in ascending-original-id order with the index's own seed — the
  result is field-for-field the index a fresh ``build_bss`` over the same
  live rows would produce (ids preserved through a permutation remap), the
  anchor of the bit-identity contract below; with ``refresh_pivots=False``
  it keeps the reference tables and only re-permutes / re-packs (cheaper:
  no pivot selection pass, ``m × P`` projection distances).
  :func:`maybe_compact` is the threshold policy.

Every mutation is FUNCTIONAL: it returns a NEW ``BSSIndex`` (plus a
:class:`MutationStats`) sharing the unchanged arrays, and bumps the
monotonic ``index.generation``.  A generation is therefore a consistent
snapshot — the serving front mutates by swapping whole index references
between micro-batches (queries in flight finish on the old mirror; no
torn reads) and keys its exact-hit cache on the generation.

Exactness contract: at EVERY generation, the fused / oracle / sharded /
bf16 engines agree bit-for-bit on hits, kNN results and per-query distance
counts (engine parity is layout-independent: they share one layout and one
bound definition).  After :func:`compact` with refreshed pivots, the index
is additionally bit-identical — layout, hits, counts — to a fresh
``build_bss`` over the same live rows.  An un-compacted append keeps old
blocks verbatim instead of re-permuting (that is what makes it O(m)), so
its BLOCK LAYOUT legitimately differs from a fresh build until compaction;
``tests/test_maintain.py`` pins all three statements.

Everything here is host-side numpy orchestration (never jit-reachable);
the only device work is mirror extension.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

import jax.numpy as jnp
import numpy as np

from repro.core.flat_index import (
    BSSDeviceArrays,
    BSSIndex,
    _build_engine_index,
    _engine_metric,
    _pack_blocks,
    _project_all,
    _split_perm,
    _MIN_NORM,
)
from repro.core.npdist import pairwise_np

__all__ = [
    "MutationStats",
    "append",
    "delete",
    "compact",
    "maybe_compact",
]


@dataclasses.dataclass(frozen=True)
class MutationStats:
    """What one mutation did and what it cost — the accounting the
    no-full-rebuild contract is verified by, and the record the serving
    front folds into its metrics registry.

    ``table_dists`` counts the host-side reference-table distance
    evaluations the mutation performed: ``rows × n_pivots`` for append
    (new rows only — the proof the append path never re-derives the
    existing corpus), 0 for delete, the live-corpus projection cost for
    compact."""

    op: str                    # "append" | "delete" | "compact"
    generation: int            # the NEW index's generation
    rows: int                  # rows appended / deleted / re-packed
    table_dists: int           # host reference-table distance evaluations
    n_blocks: int              # the NEW index's block count
    tombstone_frac: float      # the NEW index's tombstone fraction
    new_blocks: int = 0        # append: blocks added
    sharded_in_place: bool = False  # append: mirror spliced, no re-layout
    refreshed_pivots: bool = False  # compact: pivot tables re-derived


def _engine_rows(index: BSSIndex, rows: np.ndarray) -> np.ndarray:
    """Map raw input rows into the index's engine space — the same ops (and
    therefore the same bits) as ``build_bss``'s corpus-side mapping."""
    rows = np.asarray(rows, np.float32)
    if rows.ndim != 2 or rows.shape[1] != index.data.shape[1]:
        raise ValueError(
            f"rows must have shape (m, {index.data.shape[1]}), got "
            f"{rows.shape}"
        )
    if index.metric_name == "cosine":
        norms = np.linalg.norm(rows, axis=1, keepdims=True)
        rows = rows / np.maximum(norms, _MIN_NORM)
    return rows


def _layout_rows(
    index: BSSIndex, rows: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Lay engine-space rows out against the index's EXISTING reference
    tables: project onto the pivot-pair planes, median-split for locality,
    pack into fresh padded blocks with their boxes — the exact helpers
    ``build_bss`` itself runs, over the new rows only.  Returns
    ``(perm, data_pad, valid, boxes, table_dists)`` where ``perm`` orders
    the INPUT rows and ``table_dists`` is the pivot-distance count."""
    build_metric = _engine_metric(index.metric_name)
    dp = pairwise_np(build_metric, rows, index.pivots).astype(np.float32)
    x, y = _project_all(dp, index.pairs, index.deltas)
    feats = np.concatenate([x, y], axis=1)
    perm = _split_perm(feats, index.block)
    data_pad, valid, boxes = _pack_blocks(
        rows[perm], x[perm], y[perm], index.block
    )
    return perm, data_pad, valid, boxes, int(dp.size)


def append(
    index: BSSIndex, rows: np.ndarray
) -> tuple[BSSIndex, MutationStats]:
    """Append ``rows`` as fresh blocks; returns ``(new_index, stats)``.

    The new rows are assigned original ids ``[index.next_id,
    index.next_id + m)`` (stable across later compactions), laid out
    against the EXISTING pivots (module docstring), and appended after the
    current blocks.  Existing blocks — data, boxes, validity — are shared
    untouched; live device mirrors are extended, not rebuilt."""
    rows = _engine_rows(index, rows)
    m = rows.shape[0]
    if m == 0:
        raise ValueError("append needs at least one row")
    perm_new, tail_data, tail_valid, tail_boxes, table_dists = _layout_rows(
        index, rows
    )
    ids = index.next_id + np.arange(m, dtype=np.int64)
    pad = tail_valid.shape[0] - m
    tail_perm = np.concatenate(
        [ids[perm_new], np.full(pad, -1, dtype=np.int64)]
    )

    new = dataclasses.replace(
        index,
        data=np.concatenate([index.data, tail_data]),
        perm=np.concatenate([index.perm, tail_perm]),
        valid=np.concatenate([index.valid, tail_valid]),
        boxes=np.concatenate([index.boxes, tail_boxes], axis=2),
        generation=index.generation + 1,
        next_id=index.next_id + m,
        _device=None,
        _sharded=None,
        _bf16=None,
        # the margin is a corpus max — new rows can raise it; recompute
        # lazily on first bf16 query of the new generation
        _bf16_eps=None,
    )

    # device-mirror extension: only the new blocks cross host→device
    if index._device is not None:
        old = index._device
        new._device = BSSDeviceArrays(
            data=jnp.concatenate(
                [old.data, jnp.asarray(tail_data, jnp.float32)]
            ),
            pivots=old.pivots,
            pairs=old.pairs,
            deltas=old.deltas,
            boxes=jnp.concatenate(
                [old.boxes, jnp.asarray(tail_boxes, jnp.float32)], axis=2
            ),
            valid=jnp.concatenate([old.valid, jnp.asarray(tail_valid)]),
        )
    if index._bf16 is not None:
        new._bf16 = jnp.concatenate(
            [index._bf16, jnp.asarray(tail_data, jnp.bfloat16)]
        )
    sharded_in_place = False
    if index._sharded is not None:
        ext = index._sharded.extended(
            new, tail_data, tail_valid, tail_boxes, tail_perm
        )
        if ext is not None:
            new._sharded = ext
            sharded_in_place = True

    return new, MutationStats(
        op="append",
        generation=new.generation,
        rows=m,
        table_dists=table_dists,
        n_blocks=new.n_blocks,
        tombstone_frac=new.tombstone_frac,
        new_blocks=tail_boxes.shape[2],
        sharded_in_place=sharded_in_place,
    )


def delete(
    index: BSSIndex, ids: Iterable[int]
) -> tuple[BSSIndex, MutationStats]:
    """Tombstone rows by ORIGINAL id; returns ``(new_index, stats)``.

    A deleted slot clears its ``valid`` bit (every engine's masked exact
    phase, hit test and per-block distance accounting honour it already)
    and its ``perm`` entry becomes the -1 padding sentinel.  Unknown or
    already-deleted ids raise ``ValueError`` — a delete is an assertion
    about a live row, and silently ignoring a stale id would hide a
    double-delete race in the caller."""
    want = np.asarray(list(ids), dtype=np.int64)
    if want.size == 0:
        raise ValueError("delete needs at least one id")
    if np.unique(want).size != want.size:
        raise ValueError("duplicate ids in one delete")
    # original id -> slot position (live rows only)
    live_pos = np.nonzero(index.valid)[0]
    live_ids = index.perm[live_pos]
    id2pos = np.full(index.next_id, -1, dtype=np.int64)
    id2pos[live_ids] = live_pos
    bad = (want < 0) | (want >= index.next_id)
    if bad.any():
        raise ValueError(f"unknown ids: {want[bad].tolist()}")
    pos = id2pos[want]
    dead = pos < 0
    if dead.any():
        raise ValueError(
            f"ids not live (unknown or already deleted): "
            f"{want[dead].tolist()}"
        )

    valid = index.valid.copy()
    valid[pos] = False
    perm = index.perm.copy()
    perm[pos] = -1
    new = dataclasses.replace(
        index,
        perm=perm,
        valid=valid,
        generation=index.generation + 1,
        tombstones=index.tombstones + int(want.size),
        _device=None,
        _sharded=None,
        # data is untouched: the bf16 mirror stays valid, and the old
        # margin (a max over a SUPERSET of the live rows) remains sound —
        # a larger eps only widens the fp32 re-check band
        _bf16=index._bf16,
        _bf16_eps=index._bf16_eps,
    )
    if index._device is not None:
        new._device = index._device._replace(
            valid=index._device.valid.at[jnp.asarray(pos)].set(False)
        )
    if index._sharded is not None:
        new._sharded = index._sharded.with_tombstones(new, pos)

    return new, MutationStats(
        op="delete",
        generation=new.generation,
        rows=int(want.size),
        table_dists=0,
        n_blocks=new.n_blocks,
        tombstone_frac=new.tombstone_frac,
    )


def compact(
    index: BSSIndex, *, refresh_pivots: bool = True
) -> tuple[BSSIndex, MutationStats]:
    """Re-permute the live rows into a fresh tight layout; returns
    ``(new_index, stats)``.  Original ids survive (``next_id`` too, so
    id assignment never collides with resurrected slots); tombstones
    reset.

    ``refresh_pivots=True`` reruns the FULL build over the live rows in
    ascending-original-id order with the index's own seed — field-for-field
    the fresh ``build_bss`` over the same live rows (the bit-identity
    anchor; see module docstring).  ``refresh_pivots=False`` keeps the
    existing reference tables and only re-permutes / re-packs — the cheap
    variant for when exclusion power is still healthy."""
    live_pos = np.nonzero(index.valid)[0]
    m = live_pos.size
    if m == 0:
        raise ValueError("compact needs at least one live row")
    live_ids = index.perm[live_pos]
    order = np.argsort(live_ids)
    ids_sorted = live_ids[order]
    rows = index.data[live_pos[order]]  # engine space, ascending id

    if refresh_pivots:
        built = _build_engine_index(
            index.metric_name, rows,
            n_pivots=index.pivots.shape[0],
            n_pairs=index.pairs.shape[0],
            block=index.block, seed=index.seed, mesh=index.mesh,
        )
        perm = built.perm
        data_pad, valid, boxes = built.data, built.valid, built.boxes
        pivots, pairs, deltas = built.pivots, built.pairs, built.deltas
        # FFT selection evaluates O(m·P) candidate distances plus the m·P
        # projection table — charge both halves
        table_dists = 2 * m * index.pivots.shape[0]
    else:
        perm_rows, data_pad, valid, boxes, table_dists = _layout_rows(
            index, rows
        )
        pad = valid.shape[0] - m
        perm = np.concatenate(
            [perm_rows, np.full(pad, -1, dtype=np.int64)]
        )
        pivots, pairs, deltas = index.pivots, index.pairs, index.deltas

    # row positions -> original ids (fresh-build comparisons map through
    # the same ids_sorted table)
    perm_ids = np.where(perm >= 0, ids_sorted[np.clip(perm, 0, m - 1)], -1)
    new = dataclasses.replace(
        index,
        data=data_pad,
        perm=perm_ids,
        valid=valid,
        pivots=pivots,
        pairs=pairs,
        deltas=deltas,
        boxes=boxes,
        generation=index.generation + 1,
        tombstones=0,
        _device=None,
        _sharded=None,
        _bf16=None,
        _bf16_eps=None,
    )
    return new, MutationStats(
        op="compact",
        generation=new.generation,
        rows=m,
        table_dists=int(table_dists),
        n_blocks=new.n_blocks,
        tombstone_frac=0.0,
        refreshed_pivots=refresh_pivots,
    )


def maybe_compact(
    index: BSSIndex,
    *,
    max_tombstone_frac: float = 0.25,
    max_block_growth: float = 2.0,
    block_exclusion_rate: float | None = None,
    min_block_exclusion_rate: float = 0.5,
    refresh_pivots: bool | None = None,
) -> tuple[BSSIndex, MutationStats | None]:
    """Compact when the layout has degraded; returns ``(index, stats)``
    with ``stats=None`` (and the index unchanged) when it has not.

    Triggers: tombstone fraction above ``max_tombstone_frac``, or block
    count above ``max_block_growth ×`` the minimum the live rows need
    (append always opens fresh blocks, so growth measures fragmentation).

    Pivot refresh: pass the measured ``block_exclusion_rate`` from the
    engines' stats (PR 8's attribution metrics export it) and the pivots
    are re-derived when it has sunk below ``min_block_exclusion_rate`` —
    appended data drifting away from the original pivots is exactly what
    that shows up as.  ``refresh_pivots`` forces the choice either way."""
    n_live = index.n_valid
    min_blocks = max(1, -(-n_live // index.block))
    degraded = (
        index.tombstone_frac > max_tombstone_frac
        or index.n_blocks > max_block_growth * min_blocks
    )
    if not degraded:
        return index, None
    if refresh_pivots is None:
        refresh_pivots = (
            block_exclusion_rate is not None
            and block_exclusion_rate < min_block_exclusion_rate
        )
    return compact(index, refresh_pivots=refresh_pivots)
