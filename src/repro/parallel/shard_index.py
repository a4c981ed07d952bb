"""Sharded BSS: the fused engine partitioned over a device mesh.

``ShardedBSSIndex`` takes a built :class:`~repro.core.flat_index.BSSIndex`
and partitions its corpus BLOCKS across the mesh's data axes — block-granular,
so every shard is itself a valid blocked kernel corpus (block-aligned data
rows, per-block boxes, per-slot validity).  Queries and the reference-point
tables (pivots, pairs, deltas) are replicated; the shard-local arrays are
born with a ``NamedSharding`` once, so repeated queries pay no per-call
re-layout.

Query paths (both run the EXISTING fused single-device code shard-local
under ``shard_map`` — same kernels, same bound math, same masking):

* ``sharded_query_batched`` — range search.  Each shard runs the fused pass
  (planar lower bound -> tile survival -> masked exact phase) over its own
  blocks and emits a per-shard hit BITMASK; the out-spec concatenates the
  bitmasks back in corpus order, so host-side hit extraction is identical to
  the single-device engine's.

* ``sharded_knn_batched`` — radius-deepening kNN.  Every round each shard
  computes its masked exact distances and a per-shard ``lax.top_k``; the
  cross-device merge all-gathers the (distance, global position) candidate
  lists and runs a second ``top_k`` over the concatenation.  The shrinking
  radius stays GLOBAL (driven by the merged kth-nearest-so-far), so each
  shard's planar exclusion remains sound — a shard never prunes a block some
  other shard's candidates couldn't already beat.  The host driver mirrors
  ``bss_knn_batched``'s radius schedule step for step, which is what makes
  the per-query distance accounting identical to the single-device engine.

Shard telemetry: every query path also returns per-shard exact-phase
distance counts and surviving-block counts (``stats["shard_dists"]`` /
``stats["shard_blocks"]``, one slot per shard) as FUNCTIONAL jit outputs
— tiny shard-local reductions concatenated by the out-spec, never a
callback, so the jaxpr audit's no-callback and bit-identity contracts
hold unchanged.  The serving layer folds them into ``shard/imbalance``
gauges (``repro.obs.fold.shard_imbalance``).

Block-count padding: when ``n_blocks`` is not a multiple of the shard
count, empty padding blocks are appended — zero data rows marked invalid,
and boxes carrying the same ``EMPTY_BOX`` sentinel (min=+big, max=-big) a
fully-padded block would get in ``build_bss``, so their planar bound is
+inf and they are excluded at any finite radius.  All stats are reported
over the REAL blocks only; results and per-query distance counts are
asserted (tests, benchmarks) to be identical to the single-device fused
engine and the numpy oracle.

Tie-breaking note: ``lax.top_k`` prefers the earliest index on equal
values.  The merge concatenates candidate lists shard-major (shard 0's
candidates first, each list in ascending-position order for ties), so on
equal distances the merged ``top_k`` selects the smallest global position —
exactly the single-device ``top_k``'s choice.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.backends import (
    EngineOpts,
    resolve_backend,
    resolve_engine_opts,
    tile_survival,
)
from repro.core.flat_index import (
    _DEFAULT_BQ,
    _batched_stats,
    _bf16_stats,
    _engine_metric,
    _engine_queries,
    _finish_stats,
    _fused_lower_bounds,
    _knn_empty_stats,
    _masked_exact_dists,
    _per_query_t,
    _valid_per_block,
    BSSDeviceArrays,
    BSSIndex,
)
from repro.kernels.planar_exclusion import EMPTY_BOX
from repro.parallel.sharding import dp_axes, named

__all__ = [
    "ShardedBSSIndex",
    "shard_bss",
    "sharded_query_batched",
    "sharded_knn_batched",
]

def _shard_work(alive, valid_l, block):
    """Shard-local work summary, as functional jit outputs (shape (1,)
    each, concatenated to (n_shards,) by the out-spec — never a callback).

    ``sdist`` is this shard's exact-phase distance-evaluation count: for
    every (query, surviving block) pair, the block's valid-row count —
    the shard-local slice of the very sum ``_batched_stats`` charges per
    query, so the shard vector totals to the batch's exact-phase work.
    ``sblk`` counts surviving NON-EMPTY blocks (a padding or fully
    tombstoned block admitted by an infinite radius does no work and is
    not this gauge's business).  int32 like the engines' other traced
    tallies (x64 stays off).
    """
    valid_pb = jnp.sum(
        valid_l.reshape(-1, block), axis=1, dtype=jnp.int32
    )
    sdist = jnp.sum(alive * valid_pb[None, :], dtype=jnp.int32)
    sblk = jnp.sum(alive & (valid_pb > 0)[None, :], dtype=jnp.int32)
    return sdist.reshape(1), sblk.reshape(1)


class ShardedBSSIndex:
    """Block-granular partition of a built ``BSSIndex`` over a device mesh.

    The mesh must expose at least one data axis (``("data",)`` — or
    ``("pod", "data")``, over whose product the blocks are partitioned).
    Construction pads the block count up to a multiple of the shard count,
    places the padded arrays with their ``NamedSharding`` once, and caches
    the jitted ``shard_map`` callables per (path, metric, backend) key.
    """

    def __init__(self, index: BSSIndex, mesh: Mesh):
        axes = dp_axes(mesh)
        if not axes:
            raise ValueError(
                f"mesh {mesh.axis_names} has no data axis; the sharded BSS "
                f"engine partitions corpus blocks over ('data',) (optionally "
                f"('pod', 'data'))"
            )
        self.index = index
        self.mesh = mesh
        self.axes = axes
        self.n_shards = int(np.prod([mesh.shape[a] for a in axes]))

        block = index.block
        n_blocks = index.n_blocks
        self.n_blocks_pad = -(-n_blocks // self.n_shards) * self.n_shards
        pad_b = self.n_blocks_pad - n_blocks
        dim = index.data.shape[1]
        m = index.pairs.shape[0]
        data = index.data
        valid = index.valid
        boxes = index.boxes
        perm = index.perm
        if pad_b:
            data = np.concatenate(
                [data, np.zeros((pad_b * block, dim), np.float32)]
            )
            valid = np.concatenate([valid, np.zeros(pad_b * block, bool)])
            empty = np.broadcast_to(
                np.array(EMPTY_BOX, np.float32)[:, None, None], (4, m, pad_b)
            )
            boxes = np.concatenate([boxes, empty], axis=2)
            perm = np.concatenate(
                [perm, np.full(pad_b * block, -1, np.int64)]
            )
        # original ids for the padded layout (padding slots are -1, exactly
        # like the partial-block padding of the single-device layout)
        self.perm = perm
        self.n_pad = self.n_blocks_pad * block
        self.rows_per_shard = self.n_pad // self.n_shards

        put = lambda a, spec: jax.device_put(a, named(mesh, spec))  # noqa: E731
        self.dev = BSSDeviceArrays(
            data=put(jnp.asarray(data, jnp.float32), P(axes, None)),
            pivots=put(jnp.asarray(index.pivots, jnp.float32), P()),
            pairs=put(jnp.asarray(index.pairs, jnp.int32), P()),
            deltas=put(jnp.asarray(index.deltas, jnp.float32), P()),
            boxes=put(jnp.asarray(boxes, jnp.float32), P(None, None, axes)),
            valid=put(jnp.asarray(valid), P(axes)),
        )
        self._host_data = data  # padded layout, for the lazy bf16 mirror
        self._data16: jnp.ndarray | None = None
        self._fns: dict = {}

    @property
    def dev_data16(self) -> jnp.ndarray:
        """Sharded bfloat16 corpus mirror (lazy — only bf16 queries pay for
        it), partitioned exactly like ``dev.data``.  The comparison margin
        comes from ``self.index.bf16_margin()``: it is measured over the
        VALID rows only, which the block-count padding never adds to."""
        if self._data16 is None:
            self._data16 = jax.device_put(
                jnp.asarray(self._host_data, jnp.bfloat16),
                named(self.mesh, P(self.axes, None)),
            )
        return self._data16

    # ------------------------------------------------------------- callables

    def _range_fn(self, metric: str, backend: str, bq: int, interpret):
        key = ("range", metric, backend, bq, interpret)
        if key not in self._fns:
            axes, block = self.axes, self.index.block

            def local(q, t, data_l, valid_l, boxes_l, pivots, pairs, deltas):
                # t is the replicated (Q,) per-query radius vector — each
                # query's survival and hit test use only its own radius,
                # exactly like the single-device engine
                lb = _fused_lower_bounds(
                    metric, q, pivots, pairs, deltas, boxes_l,
                    backend=backend, bq=bq, interpret=interpret,
                )
                alive = lb <= t[:, None]
                tmask = tile_survival(alive, bq)
                dist = _masked_exact_dists(
                    metric, q, data_l, valid_l, tmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )
                sdist, sblk = _shard_work(alive, valid_l, block)
                return dist <= t[:, None], alive, tmask, sdist, sblk

            self._fns[key] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(
                    P(), P(), P(axes, None), P(axes), P(None, None, axes),
                    P(), P(), P(),
                ),
                out_specs=(
                    P(None, axes), P(None, axes), P(None, axes),
                    P(axes), P(axes),
                ),
                check_vma=False,
            ))
        return self._fns[key]

    def _range_bf16_fn(self, metric: str, backend: str, bq: int, interpret):
        key = ("range16", metric, backend, bq, interpret)
        if key not in self._fns:
            axes, block = self.axes, self.index.block

            def local(q, t, eps, data_l, valid_l, boxes_l, pivots, pairs,
                      deltas, data16_l):
                # shard-local bf16 scan + fp32 boundary re-check: the same
                # sure/band/re-check scheme as _query_batched_bf16_jit, run
                # over this shard's blocks.  The re-check is purely local
                # (a band cell's fp32 value lives on the shard that owns
                # the block), so no extra collectives appear — the bitmask
                # concatenation below IS the global fp32 merge.
                lb = _fused_lower_bounds(
                    metric, q, pivots, pairs, deltas, boxes_l,
                    backend=backend, bq=bq, interpret=interpret,
                )
                alive = lb <= t[:, None]
                tmask = tile_survival(alive, bq)
                d16 = _masked_exact_dists(
                    metric, q, data16_l, valid_l, tmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )
                t_col = t[:, None]
                sure = d16 <= t_col - eps
                band = (d16 <= t_col + eps) & ~sure
                band_blocks = band.reshape(q.shape[0], -1, block).any(axis=2)
                rmask = tile_survival(band_blocks, bq) & tmask
                d32 = _masked_exact_dists(
                    metric, q, data_l, valid_l, rmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )
                hit = sure | (band & (d32 <= t_col))
                sdist, sblk = _shard_work(alive, valid_l, block)
                return (
                    hit, alive, tmask, rmask,
                    jnp.sum(band, axis=1, dtype=jnp.int32)[:, None],
                    sdist, sblk,
                )

            self._fns[key] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(
                    P(), P(), P(), P(axes, None), P(axes),
                    P(None, None, axes), P(), P(), P(), P(axes, None),
                ),
                out_specs=(
                    P(None, axes), P(None, axes), P(None, axes),
                    P(None, axes), P(None, axes), P(axes), P(axes),
                ),
                check_vma=False,
            ))
        return self._fns[key]

    def _lb_fn(self, metric: str, backend: str, bq: int, interpret):
        key = ("lb", metric, backend, bq, interpret)
        if key not in self._fns:
            axes = self.axes

            def local(q, boxes_l, pivots, pairs, deltas):
                return _fused_lower_bounds(
                    metric, q, pivots, pairs, deltas, boxes_l,
                    backend=backend, bq=bq, interpret=interpret,
                )

            self._fns[key] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(None, None, axes), P(), P(), P()),
                out_specs=P(None, axes),
                check_vma=False,
            ))
        return self._fns[key]

    def _knn_round_fn(self, metric: str, backend: str, bq: int, interpret,
                      k: int):
        key = ("knn", metric, backend, bq, interpret, k)
        if key not in self._fns:
            axes, block = self.axes, self.index.block
            mesh, rows = self.mesh, self.rows_per_shard
            # a shard can contribute at most min(k, rows) candidates of the
            # true global top-k, so the per-shard top_k (and the all-gather)
            # can stay that narrow even when k exceeds a shard's row count
            k_local = min(k, rows)

            def local(q, radii, lb_l, data_l, valid_l):
                alive = lb_l <= radii[:, None]
                tmask = tile_survival(alive, bq)
                dist = _masked_exact_dists(
                    metric, q, data_l, valid_l, tmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )  # (Q, rows), +inf where pruned/padding
                neg, li = jax.lax.top_k(-dist, k_local)
                # local -> global positions in the padded permuted layout
                off = jnp.int32(0)
                for a in axes:
                    off = off * mesh.shape[a] + jax.lax.axis_index(a)
                gi = li + off * rows
                allneg = jax.lax.all_gather(neg, axes)  # (S, Q, k_local)
                allidx = jax.lax.all_gather(gi, axes)
                nq = q.shape[0]
                allneg = jnp.moveaxis(allneg, 0, 1).reshape(nq, -1)
                allidx = jnp.moveaxis(allidx, 0, 1).reshape(nq, -1)
                neg2, sel = jax.lax.top_k(allneg, k)  # global k smallest
                cand_idx = jnp.take_along_axis(allidx, sel, axis=1)
                sdist, sblk = _shard_work(alive, valid_l, block)
                return cand_idx, -neg2, alive, tmask, sdist, sblk

            self._fns[key] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(
                    P(), P(), P(None, axes), P(axes, None), P(axes),
                ),
                out_specs=(
                    P(None, None), P(None, None), P(None, axes),
                    P(None, axes), P(axes), P(axes),
                ),
                check_vma=False,
            ))
        return self._fns[key]

    def _knn_round_bf16_fn(self, metric: str, backend: str, bq: int,
                           interpret, k: int):
        key = ("knn16", metric, backend, bq, interpret, k)
        if key not in self._fns:
            axes, block = self.axes, self.index.block
            mesh, rows = self.mesh, self.rows_per_shard
            k_local = min(k, rows)

            def local(q, radii, eps, lb_l, data_l, valid_l, data16_l):
                # bf16 scan, then a FIRST all-gather to form the GLOBAL bf16
                # kth — the re-check band must be global or a shard whose
                # own kth16 is loose would re-check too little.  Band cells
                # are re-checked locally against the fp32 shard, and the
                # per-shard top_k over the band-restricted fp32 values feeds
                # the STANDARD merge: every cell at or under the global fp32
                # kth is in the band (margin containment), cells outside are
                # strictly beyond it, and shard-major concatenation keeps
                # the fp32 engine's tie order — outputs are bit-identical
                # to _knn_round_fn.
                alive = lb_l <= radii[:, None]
                tmask = tile_survival(alive, bq)
                d16 = _masked_exact_dists(
                    metric, q, data16_l, valid_l, tmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )
                nq = q.shape[0]
                neg16, _ = jax.lax.top_k(-d16, k_local)
                allneg16 = jax.lax.all_gather(neg16, axes)  # (S, Q, k_local)
                allneg16 = jnp.moveaxis(allneg16, 0, 1).reshape(nq, -1)
                merged16, _ = jax.lax.top_k(allneg16, k)
                kth16 = -merged16[:, -1]
                bthr = jnp.where(
                    jnp.isfinite(kth16), kth16 + 2.0 * eps, jnp.inf
                )
                band = (d16 <= bthr[:, None]) & jnp.isfinite(d16)
                band_blocks = band.reshape(nq, -1, block).any(axis=2)
                rmask = tile_survival(band_blocks, bq) & tmask
                d32 = _masked_exact_dists(
                    metric, q, data_l, valid_l, rmask,
                    backend=backend, block=block, bq=bq, interpret=interpret,
                )
                dist = jnp.where(band, d32, jnp.inf)
                neg, li = jax.lax.top_k(-dist, k_local)
                off = jnp.int32(0)
                for a in axes:
                    off = off * mesh.shape[a] + jax.lax.axis_index(a)
                gi = li + off * rows
                allneg = jax.lax.all_gather(neg, axes)
                allidx = jax.lax.all_gather(gi, axes)
                allneg = jnp.moveaxis(allneg, 0, 1).reshape(nq, -1)
                allidx = jnp.moveaxis(allidx, 0, 1).reshape(nq, -1)
                neg2, sel = jax.lax.top_k(allneg, k)
                cand_idx = jnp.take_along_axis(allidx, sel, axis=1)
                sdist, sblk = _shard_work(alive, valid_l, block)
                return (
                    cand_idx, -neg2, alive, tmask, rmask,
                    jnp.sum(band, axis=1, dtype=jnp.int32)[:, None],
                    sdist, sblk,
                )

            self._fns[key] = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(
                    P(), P(), P(), P(None, axes), P(axes, None), P(axes),
                    P(axes, None),
                ),
                out_specs=(
                    P(None, None), P(None, None), P(None, axes),
                    P(None, axes), P(None, axes), P(None, axes),
                    P(axes), P(axes),
                ),
                check_vma=False,
            ))
        return self._fns[key]


    # --------------------------------------------------- living-corpus hooks

    def _clone_for(self, new_index: BSSIndex) -> "ShardedBSSIndex":
        """Shallow clone bound to a mutated index.  The jitted shard_map
        cache (``_fns``) is SHARED — its closures capture only mesh
        geometry and static knobs, and take the device arrays as call
        arguments, so a mutation that preserves array shapes keeps serving
        with zero recompiles."""
        clone = object.__new__(ShardedBSSIndex)
        clone.__dict__.update(self.__dict__)
        clone.index = new_index
        return clone

    def _spliced(self, arr: jnp.ndarray, tail: np.ndarray, start: int,
                 dtype, axis: int = 0) -> jnp.ndarray:
        """Device-side in-place-style update of a sharded array (a fresh
        buffer, but updated ON the devices) with the output pinned to the
        array's own NamedSharding — the splice never gathers the corpus to
        one device and never re-lands the unchanged blocks."""
        fn = jax.jit(
            lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                a, b, start, axis
            ),
            out_shardings=arr.sharding,
        )
        return fn(arr, jnp.asarray(tail, dtype))

    def extended(
        self,
        new_index: BSSIndex,
        tail_data: np.ndarray,
        tail_valid: np.ndarray,
        tail_boxes: np.ndarray,
        tail_perm: np.ndarray,
    ) -> "ShardedBSSIndex | None":
        """Consume empty PADDING blocks for an append's fresh blocks.

        The padded layout parks its empty blocks at the absolute end of
        the block axis — on the least-loaded shard(s), since the partition
        is contiguous-chunk.  When the new blocks fit in that free space
        they are spliced into those slots device-side: no array changes
        shape, the contiguous partition (and ``rows_per_shard``) is
        untouched, and the shared ``_fns`` cache keeps every compiled
        engine hot.  Returns ``None`` when they do NOT fit — the caller
        falls back to a lazy full re-layout (the block count must grow,
        which moves every chunk boundary)."""
        nb_new = tail_boxes.shape[2]
        free = self.n_blocks_pad - self.index.n_blocks
        if nb_new > free:
            return None
        block = self.index.block
        start_blk = self.index.n_blocks
        start_row = start_blk * block
        nrows = nb_new * block
        clone = self._clone_for(new_index)
        perm = self.perm.copy()
        perm[start_row : start_row + nrows] = tail_perm
        clone.perm = perm
        host = self._host_data.copy()
        host[start_row : start_row + nrows] = tail_data
        clone._host_data = host
        clone.dev = BSSDeviceArrays(
            data=self._spliced(
                self.dev.data, tail_data, start_row, jnp.float32
            ),
            pivots=self.dev.pivots,
            pairs=self.dev.pairs,
            deltas=self.dev.deltas,
            boxes=self._spliced(
                self.dev.boxes, tail_boxes, start_blk, jnp.float32, axis=2
            ),
            valid=self._spliced(
                self.dev.valid, tail_valid, start_row, jnp.bool_
            ),
        )
        if self._data16 is not None:
            clone._data16 = self._spliced(
                self._data16, tail_data, start_row, jnp.bfloat16
            )
        return clone

    def with_tombstones(
        self, new_index: BSSIndex, positions: np.ndarray
    ) -> "ShardedBSSIndex":
        """Clear the valid bits of deleted slot positions on-device (data,
        boxes and the bf16 mirror are untouched — the engines mask by
        validity) and mirror the -1 perm sentinel on the host side."""
        clone = self._clone_for(new_index)
        perm = self.perm.copy()
        perm[positions] = -1
        clone.perm = perm
        fn = jax.jit(
            lambda v, p: v.at[p].set(False),
            out_shardings=self.dev.valid.sharding,
        )
        clone.dev = self.dev._replace(
            valid=fn(self.dev.valid, jnp.asarray(positions))
        )
        return clone


def shard_bss(index: BSSIndex, mesh: Mesh) -> ShardedBSSIndex:
    """Partition a built index's blocks over the mesh (see class docs)."""
    return ShardedBSSIndex(index, mesh)


# ---------------------------------------------------------------------------
# Range search
# ---------------------------------------------------------------------------


def sharded_query_batched(
    sidx: ShardedBSSIndex,
    queries: np.ndarray,
    t,
    *,
    opts: EngineOpts | None = None,
    bq: int | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    precision: str | None = None,
) -> tuple[list[list[int]], dict]:
    """Exact range search, one fused shard-local pass per device.

    Engine options travel as ``opts=EngineOpts(...)`` (legacy per-knob
    kwargs shimmed via ``resolve_engine_opts``); ``opts.realisation`` is
    ignored — the shard-local body is always the dense masked pass, whose
    fixed shapes are what keep per-device compiles bounded.

    ``t`` is a scalar threshold or a (Q,) vector of per-query radii (the
    serving front's mixed-threshold micro-batches; a negative radius —
    padding — excludes its row everywhere), replicated across shards.

    Hit lists (indices AND per-query order) and the distance accounting are
    identical to ``bss_query_batched`` / the numpy oracle: the per-shard
    planar bounds are the same elementwise math over a block slice, and the
    concatenated hit bitmask is extracted exactly like the single-device
    dense path's.  ``precision="bf16"`` runs the shard-local bf16 scan with
    fp32 boundary re-check (``_range_bf16_fn``) — same results, same
    counts, with the re-check telemetry added to stats."""
    opts = resolve_engine_opts(
        opts, bq=bq, backend=backend, interpret=interpret,
        precision=precision,
    )
    bq = opts.bq if opts.bq is not None else _DEFAULT_BQ
    interpret = opts.interpret
    precision = opts.precision
    backend = resolve_backend(opts.backend)
    index = sidx.index
    metric_eng = _engine_metric(index.metric_name)
    queries = _engine_queries(index.metric_name, np.asarray(queries, np.float32))
    nq = queries.shape[0]
    if nq == 0:
        empty = np.zeros((0, index.n_blocks), bool)
        stats = _batched_stats(index, empty, empty)
        stats["n_shards"] = sidx.n_shards
        stats["shard_dists"] = np.zeros(sidx.n_shards, np.int64)
        stats["shard_blocks"] = np.zeros(sidx.n_shards, np.int64)
        stats["precision"] = precision
        if precision == "bf16":
            _bf16_stats(stats, index.bf16_margin(), 0, np.zeros(0, np.int64))
        return [], _finish_stats(
            stats, kind="range", backend=backend, engine="sharded"
        )
    t_vec = _per_query_t(t, nq)
    if precision == "bf16":
        eps = index.bf16_margin()
        fn = sidx._range_bf16_fn(metric_eng, backend, bq, interpret)
        hit, alive, tmask, rmask, band_counts, sdist, sblk = fn(
            jnp.asarray(queries), jnp.asarray(t_vec), jnp.float32(eps),
            sidx.dev.data, sidx.dev.valid, sidx.dev.boxes,
            sidx.dev.pivots, sidx.dev.pairs, sidx.dev.deltas,
            sidx.dev_data16,
        )
    else:
        fn = sidx._range_fn(metric_eng, backend, bq, interpret)
        hit, alive, tmask, sdist, sblk = fn(
            jnp.asarray(queries), jnp.asarray(t_vec),
            sidx.dev.data, sidx.dev.valid, sidx.dev.boxes,
            sidx.dev.pivots, sidx.dev.pairs, sidx.dev.deltas,
        )
    hit = np.asarray(hit)
    qidx, pidx = np.nonzero(hit)  # row-major: ascending position per query
    orig = sidx.perm[pidx]
    counts = hit.sum(axis=1)
    per_query = np.split(orig, np.cumsum(counts)[:-1])
    results = [r.tolist() for r in per_query]
    # padding-block columns are never alive (their bound is +inf); stats are
    # reported over the REAL blocks so they compare 1:1 with the
    # single-device engine and the oracle
    alive = np.asarray(alive)[:, : index.n_blocks]
    tmask = np.asarray(tmask)[:, : index.n_blocks]
    stats = _batched_stats(index, alive, tmask)
    stats["n_shards"] = sidx.n_shards
    # per-shard exact-phase work split (functional jit outputs, one slot
    # per shard): the shard totals partition the batch's exact-phase
    # distance sum, so imbalance is read straight off this vector
    stats["shard_dists"] = np.asarray(sdist, dtype=np.int64)
    stats["shard_blocks"] = np.asarray(sblk, dtype=np.int64)
    stats["precision"] = precision
    if precision == "bf16":
        _bf16_stats(
            stats, eps, int(np.asarray(rmask).sum()),
            np.asarray(band_counts).sum(axis=1),
        )
    return results, _finish_stats(
        stats, kind="range", backend=backend, engine="sharded"
    )


# ---------------------------------------------------------------------------
# kNN
# ---------------------------------------------------------------------------


def sharded_knn_batched(
    sidx: ShardedBSSIndex,
    queries: np.ndarray,
    k: int,
    *,
    r0: float | None = None,
    growth: float = 2.0,
    max_rounds: int = 8,
    opts: EngineOpts | None = None,
    bq: int | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    precision: str | None = None,
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Exact batched kNN over the sharded index.

    Engine options travel as ``opts=EngineOpts(...)`` (legacy kwargs
    shimmed; ``opts.realisation`` ignored — rounds are dense-pinned, see
    ``sharded_query_batched``); ``r0`` / ``growth`` / ``max_rounds`` are
    the radius schedule and stay explicit.

    ``precision="bf16"`` swaps each round for the bf16-scan +
    global-band + fp32-re-check round (``_knn_round_bf16_fn``); candidates,
    distances, the radius schedule and the per-query counts stay
    bit-identical to the fp32 sharded engine.

    The host driver mirrors ``bss_knn_batched`` step for step — same initial
    per-query radius (read off the sorted REAL-block bounds), same
    tighten-and-widen schedule, same exhaustive fallback — so the per-round
    alive sets over real blocks (and therefore the per-query distance
    counts) are identical to the single-device engine's.  Only the round
    body differs: each shard evaluates its own masked exact phase and a
    per-shard ``top_k``, merged across the mesh by all-gather + global
    ``top_k`` (see module docstring for the tie-break argument); the
    shrinking radius is driven by the MERGED kth-nearest-so-far, keeping
    per-shard exclusion globally sound."""
    opts = resolve_engine_opts(
        opts, bq=bq, backend=backend, interpret=interpret,
        precision=precision,
    )
    bq = opts.bq if opts.bq is not None else _DEFAULT_BQ
    interpret = opts.interpret
    precision = opts.precision
    backend = resolve_backend(opts.backend)
    index = sidx.index
    metric_eng = _engine_metric(index.metric_name)
    queries = _engine_queries(index.metric_name, np.asarray(queries, np.float32))
    nq = queries.shape[0]
    k = int(k)
    if k <= 0:
        raise ValueError(f"k must be positive, got {k}")
    if nq == 0:
        stats = _knn_empty_stats(index, 0, precision, backend,
                                 engine="sharded")
        stats["n_shards"] = sidx.n_shards
        stats["shard_dists"] = np.zeros(sidx.n_shards, np.int64)
        stats["shard_blocks"] = np.zeros(sidx.n_shards, np.int64)
        return (
            np.zeros((0, k), np.int64), np.zeros((0, k), np.float32), stats,
        )
    k_run = min(k, index.n_valid)
    if k_run == 0:
        stats = _knn_empty_stats(index, nq, precision, backend,
                                 engine="sharded")
        stats["n_shards"] = sidx.n_shards
        stats["shard_dists"] = np.zeros(sidx.n_shards, np.int64)
        stats["shard_blocks"] = np.zeros(sidx.n_shards, np.int64)
        return (
            np.full((nq, k), -1, np.int64),
            np.full((nq, k), np.inf, np.float32),
            stats,
        )
    qj = jnp.asarray(queries)
    n_blocks = index.n_blocks

    # radius-independent planar bounds, computed once shard-local and kept
    # device-sharded for the rounds; the host copy (REAL columns only —
    # padding bounds are +inf) drives the same initial-radius and widening
    # schedule as the single-device engine
    lb_dev = sidx._lb_fn(metric_eng, backend, bq, interpret)(
        qj, sidx.dev.boxes, sidx.dev.pivots, sidx.dev.pairs, sidx.dev.deltas,
    )
    lb_np = np.asarray(lb_dev)[:, :n_blocks]
    lb_sorted = np.sort(lb_np, axis=1)
    if r0 is None:
        j0 = min(n_blocks - 1, max(0, math.ceil(2 * k / index.block) - 1))
        radii = lb_sorted[:, j0].astype(np.float32)
    else:
        radii = np.full(nq, float(r0), np.float32)

    bf16 = precision == "bf16"
    eps = index.bf16_margin() if bf16 else 0.0
    if bf16:
        round_fn = sidx._knn_round_bf16_fn(
            metric_eng, backend, bq, interpret, k_run
        )
        data16 = sidx.dev_data16
    else:
        round_fn = sidx._knn_round_fn(metric_eng, backend, bq, interpret, k_run)
    valid_pb = _valid_per_block(index)
    total_exact = np.zeros(nq, np.int64)
    excl_pq = np.zeros(nq, np.int64)
    # per-shard accumulation across rounds: a finished query's radius is
    # -1 from the round after it completes, so its rows survive no block
    # and the in-jit shard sums agree with the `upd`-masked host tallies
    shard_dists = np.zeros(sidx.n_shards, np.int64)
    shard_blocks = np.zeros(sidx.n_shards, np.int64)
    tiles_total = 0
    recheck_pq = np.zeros(nq, np.int64)
    recheck_tiles_total = 0
    done = np.zeros(nq, bool)
    cand_idx = np.full((nq, k_run), 0, np.int64)
    cand_dist = np.full((nq, k_run), np.inf, np.float32)
    rounds = 0
    for rounds in range(1, max_rounds + 2):
        if rounds == max_rounds + 1:
            radii = np.where(done, radii, np.inf).astype(np.float32)
        if bf16:
            ci, cd, alive, tmask, rmask, band_counts, sdist, sblk = round_fn(
                qj, jnp.asarray(radii), jnp.float32(eps), lb_dev,
                sidx.dev.data, sidx.dev.valid, data16,
            )
            recheck_tiles_total += int(
                np.asarray(rmask)[:, : n_blocks].sum()
            )
            recheck_pq += np.where(
                ~done, np.asarray(band_counts).sum(axis=1), 0
            )
        else:
            ci, cd, alive, tmask, sdist, sblk = round_fn(
                qj, jnp.asarray(radii), lb_dev, sidx.dev.data, sidx.dev.valid,
            )
        shard_dists += np.asarray(sdist, dtype=np.int64)
        shard_blocks += np.asarray(sblk, dtype=np.int64)
        ci, cd = np.asarray(ci), np.asarray(cd)
        # real-block columns only: identical to the single-device alive set
        # (padding is only ever admitted by the radius=inf fallback round,
        # where its zero valid points still contribute no distances)
        alive = np.asarray(alive)[:, :n_blocks]
        tiles_round = int(np.asarray(tmask)[:, :n_blocks].sum())
        kth = cd[:, -1]
        dn = np.isfinite(kth) & ((kth <= radii) | alive.all(axis=1))
        upd = ~done  # freeze finished queries (their results are final)
        cand_idx[upd] = ci[upd]
        cand_dist[upd] = cd[upd]
        total_exact[upd] += alive[upd].astype(np.int64) @ valid_pb
        excl_pq[upd] += n_blocks - alive[upd].sum(axis=1)
        tiles_total += tiles_round
        done = done | dn
        if done.all():
            break
        # identical tighten-and-widen schedule to bss_knn_batched
        n_alive = alive.sum(axis=1)
        j_next = np.minimum(
            n_blocks - 1,
            np.maximum(np.maximum(2 * n_alive, n_alive + 1), 1),
        )
        widened = np.maximum(lb_sorted[np.arange(nq), j_next], radii * growth)
        radii = np.where(
            done, np.float32(-1.0),
            np.where(np.isfinite(kth), np.minimum(kth, widened), widened),
        ).astype(np.float32)
        radii = np.where(
            ~done & (n_alive > n_blocks // 2), np.float32(np.inf), radii
        )

    n_pivots = index.pivots.shape[0]
    stats = {
        "rounds": rounds,
        "pivot_dists_per_query": float(n_pivots),
        "exact_dists_per_query": float(total_exact.mean()),
        "dists_per_query": float(n_pivots + total_exact.mean()),
        "per_query_dists": n_pivots + total_exact,
        "tiles_computed": tiles_total,
        "n_blocks": int(n_blocks),
        "n_shards": sidx.n_shards,
        "shard_dists": shard_dists,
        "shard_blocks": shard_blocks,
        "generation": int(index.generation),
        "precision": precision,
        "excluded": {"hilbert": excl_pq},
    }
    if bf16:
        _bf16_stats(stats, eps, recheck_tiles_total, recheck_pq)
    _finish_stats(stats, kind="knn", backend=backend, engine="sharded")
    orig = np.where(np.isfinite(cand_dist), sidx.perm[cand_idx], -1)
    if k_run < k:
        orig = np.pad(orig, ((0, 0), (0, k - k_run)), constant_values=-1)
        cand_dist = np.pad(
            cand_dist, ((0, 0), (0, k - k_run)), constant_values=np.inf
        )
    return orig, cand_dist, stats
