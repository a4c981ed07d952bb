"""Blocked Supermetric Scan (BSS) — the TPU-native realisation of the paper.

The paper's trees prune *semispaces* one node at a time with data-dependent
branching — hostile to TPUs.  BSS keeps the paper's geometry (the planar
lower bound of §3) but restructures the computation for the MXU:

  build:  choose P pivots (FFT — pivot quality barely matters under the
          four-point property, §3.3); project every point onto the M
          pivot-pair planes; recursively median-split the *margin space* to
          find a locality-preserving permutation; group points into
          MXU-tile-aligned blocks of 128; store per (block × plane) bounding
          boxes of the projected coordinates, planes-major ``(4, M, B)`` so
          the bound kernel reads lane-dense rows over blocks.

  query:  dist(q, pivots)  ->  project q onto all planes  ->  per block,
          lower-bound = max over planes of planar distance-to-box  ->
          blocks with bound > t are EXCLUDED (sound by the four-point
          property); exact distances run only for surviving blocks through
          the pairwise kernel.

Every step is dense, batched and masked: pruning whole 128-point blocks is
exactly the granularity at which a TPU can actually skip work.  Exactness is
preserved (no approximation anywhere) — this is still the paper's *exact*
search, reorganised.

Query engine architecture
-------------------------

Two query paths share one index:

* **Fused batched path** (``bss_query_batched`` / ``bss_knn_batched``) — the
  production engine.  The whole query runs inside a single jitted function:
  query→pivot distances, the planar lower bound over every (query, block)
  pair, a (query-tile × block) survival mask, and exact distances for the
  surviving cells only.  On TPU the lower bound and the masked exact phase
  are the Pallas kernels (``planar_lower_bound_kernel_call`` and the
  metric-dispatched ``masked_pairwise_kernel_call`` family); off-TPU the
  same jitted graph routes through pure-jnp math so XLA still fuses it
  (``backend="auto"`` picks per ``jax.default_backend()``; tests force
  ``"pallas"`` + ``interpret=True`` to exercise the kernel wiring
  everywhere).  The jnp exact phase is adaptive in survivor density: sparse
  survivors gather only the alive (query, block) cells — for range search
  AND for kNN rounds — while dense survivors run one pairwise pass (for l2
  the range hit test runs in the squared domain with no distance matrix
  materialised).  Compact hits / top-k candidates cross back to the host,
  never an O(Q·N) matrix.  kNN is the range reduction run as *batched
  radius deepening*: one jitted round over all queries per iteration, with
  each query's kth-nearest-so-far distance tightening its radius (and
  therefore the survival mask) for the next round, and ``jax.lax.top_k``
  extracting candidates.

* **Numpy oracle path** (``bss_query``) — the original per-block host loop,
  kept verbatim as the correctness oracle: it shares the index build and the
  lower-bound definition but evaluates the exact phase in float64 numpy.
  The test suite asserts the fused path reproduces its hit lists exactly;
  it is also the baseline the benchmarks measure the fused path against.

Metric support
--------------

Every registered four-point metric is served end to end; the engine maps
each to its *kernel space* at the boundary:

* **l2** — the native MXU path (squared-domain matmul identity).
* **cosine** — served EXACTLY as l2: the proper supermetric cosine distance
  ``sqrt(2 - 2 cos)`` *is* the Euclidean distance between unit vectors, so
  the corpus is normalised once at build and queries once per batch, and
  every downstream stage (bounds, kernels, exact phase) runs the l2 code.
* **jsd / triangular** — probability-space metrics with their own VPU tile
  kernels wired into the masked exact phase and the pivot-distance stage.
* **power transforms** (``"l1^0.5"`` …, paper §2.2) — four-point by
  construction; served through the jnp pairwise path (no tile kernel).

Distance accounting: ``exact_dists_per_query`` counts only VALID corpus
points in surviving blocks (per-block valid counts, excluding the padded
slots of partial blocks), so the paper's figure of merit matches a
``DistanceCounter`` replay exactly even when n is not a multiple of the
block size.

``BSSIndex`` stores the build products as host numpy arrays (cheap to
pickle, friendly to the oracle) and mirrors them into device arrays on
first use (``index.device``) so repeated queries pay no host→device copies.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import projection
from repro.core.backends import (
    EngineOpts,
    jit_cache_size,
    resolve_backend,
    resolve_engine_opts,
    tile_survival,
)
from repro.core.distances import Metric, _dot_t, get_metric
from repro.core.npdist import pairwise_np
from repro.core.refpoints import select_fft
from repro.kernels.pairwise_dist import (
    KERNEL_METRICS,
    masked_pairwise_kernel_call,
    pairwise_kernel_call,
)
from repro.kernels.planar_exclusion import (
    EMPTY_BOX,
    planar_lower_bound_kernel_call,
)
from repro.kernels.tiles import TILE_BQ
from repro.obs import schema as obs_schema
from repro.obs.spans import SpanLog

__all__ = [
    "BSSIndex",
    "build_bss",
    "bss_query",
    "bss_query_batched",
    "bss_knn_batched",
    "bss_lower_bounds",
]

# query-tile size: matches the Pallas kernels' row tiling (REPRO_TILE_BQ)
_DEFAULT_BQ = TILE_BQ

# Normalisation floor for the cosine→l2 mapping; matches the cosine metric's
# own floor in distances._cosine_pairwise so both paths agree bit-for-bit on
# which vectors count as zero.
_MIN_NORM = 1e-12


def _engine_metric(metric_name: str) -> str:
    """The metric the fused engine actually computes with.  Supermetric
    cosine IS l2 on the unit sphere, so cosine rides the l2 kernels; every
    other metric is served natively."""
    return "l2" if metric_name == "cosine" else metric_name


def _engine_queries(metric_name: str, queries: np.ndarray) -> np.ndarray:
    """Map queries into the engine's kernel space (unit sphere for cosine;
    identity otherwise).  The corpus side happens once, in ``build_bss``."""
    if metric_name == "cosine":
        norms = np.linalg.norm(queries, axis=-1, keepdims=True)
        queries = queries / np.maximum(norms, _MIN_NORM)
    return np.asarray(queries, np.float32)


class BSSDeviceArrays(NamedTuple):
    """Device-resident mirror of the index, built once per index."""

    data: jnp.ndarray    # (n_pad, dim)
    pivots: jnp.ndarray  # (P, dim)
    pairs: jnp.ndarray   # (M, 2)
    deltas: jnp.ndarray  # (M,)
    boxes: jnp.ndarray   # (4, M, n_blocks) planes-major
    valid: jnp.ndarray   # (n_pad,) bool


@dataclasses.dataclass
class BSSIndex:
    metric_name: str
    data: np.ndarray          # (n_pad, dim) permuted + padded
    perm: np.ndarray          # (n_pad,) original index, -1 for padding
    valid: np.ndarray         # (n_pad,) bool
    pivots: np.ndarray        # (P, dim)
    pairs: np.ndarray         # (M, 2) pivot indices per plane
    deltas: np.ndarray        # (M,)
    boxes: np.ndarray         # (4, M, n_blocks): x_lo, x_hi, y_lo, y_hi rows
    block: int
    # build provenance + living-corpus bookkeeping (repro.index.maintain):
    # mutations are FUNCTIONAL — append/delete/compact return a new index
    # sharing unchanged arrays — so a generation is a consistent snapshot
    # (the serving front swaps whole generations between micro-batches).
    seed: int = 0        # build seed; compact reuses it for layout parity
    generation: int = 0  # bumped by every append/delete/compact
    next_id: int = 0     # next original id an append will assign
    tombstones: int = 0  # rows deleted since build/last compact
    # when set, device arrays are born with a NamedSharding over the mesh's
    # data axes (corpus blocks partitioned, reference tables replicated) and
    # the batched query paths route through the sharded engine
    mesh: Mesh | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _device: BSSDeviceArrays | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _sharded: object | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # bf16 exact-phase mirror (lazy): the corpus rounded to bfloat16 for the
    # halved-HBM scan, plus the derived comparison margin.  Reference tables
    # (pivots / deltas / boxes) deliberately stay fp32: rounding them would
    # perturb the survival sets and break the bit-identical-counts contract,
    # and they are a rounding-error of the corpus traffic anyway.
    _bf16: jnp.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _bf16_eps: float | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def n_blocks(self) -> int:
        return self.boxes.shape[2]

    @property
    def n_valid(self) -> int:
        return int(self.valid.sum())

    @property
    def tombstone_frac(self) -> float:
        """Deleted fraction of the rows the layout still carries — the
        compaction trigger (``repro.index.maintain.maybe_compact``)."""
        return self.tombstones / max(self.tombstones + self.n_valid, 1)

    @property
    def metric(self) -> Metric:
        return get_metric(self.metric_name)

    @property
    def device(self) -> BSSDeviceArrays:
        """Device-resident mirror, built once.  With a mesh attached this is
        the SHARDED mirror (block count padded to the shard count, arrays
        placed with their NamedSharding at birth — never re-laid-out per
        query); without one, plain single-device arrays."""
        if self.mesh is not None:
            return self.sharded().dev
        if self._device is None:
            self._device = BSSDeviceArrays(
                data=jnp.asarray(self.data, jnp.float32),
                pivots=jnp.asarray(self.pivots, jnp.float32),
                pairs=jnp.asarray(self.pairs, jnp.int32),
                deltas=jnp.asarray(self.deltas, jnp.float32),
                boxes=jnp.asarray(self.boxes, jnp.float32),
                valid=jnp.asarray(self.valid),
            )
        return self._device

    @property
    def device_bf16(self) -> jnp.ndarray:
        """(n_pad, dim) bfloat16 corpus mirror, built once.  The tile
        kernels upcast to fp32 on entry, so streaming this halves corpus
        HBM traffic with fp32 accumulation unchanged."""
        if self._bf16 is None:
            self._bf16 = jnp.asarray(self.data, jnp.bfloat16)
        return self._bf16

    def bf16_margin(self) -> float:
        """Conservative threshold margin for the bf16 phase (derivation in
        ``repro.core.precision``): measured in the ENGINE metric over the
        engine-space corpus (already unit-normalised for cosine), computed
        once per index."""
        if self._bf16_eps is None:
            from repro.core.precision import bf16_margin

            self._bf16_eps = bf16_margin(
                _engine_metric(self.metric_name), self.data, self.valid
            )
        return self._bf16_eps

    def sharded(self, mesh: Mesh | None = None):
        """The :class:`~repro.parallel.shard_index.ShardedBSSIndex` view of
        this index over ``mesh`` (default: the mesh given at build time),
        cached per mesh."""
        mesh = mesh if mesh is not None else self.mesh
        if mesh is None:
            raise ValueError(
                "no mesh: pass one here or build with build_bss(mesh=...)"
            )
        if self._sharded is None or self._sharded.mesh is not mesh:
            from repro.parallel.shard_index import ShardedBSSIndex

            self._sharded = ShardedBSSIndex(self, mesh)
        return self._sharded


def _project_all(dp: np.ndarray, pairs: np.ndarray, deltas: np.ndarray):
    """dp: (n, P) pivot distances -> (n, M) x and (n, M) y planar coords.

    SAME implementation as the query side (``projection.project``, numpy
    namespace) — in particular degenerate planes (duplicate pivots) collapse
    to the ring (0, d1) on BOTH sides, or the box/query geometries would
    diverge unsoundly."""
    return projection.project(
        dp[:, pairs[:, 0]], dp[:, pairs[:, 1]], deltas[None, :], xp=np
    )


def _split_perm(feats: np.ndarray, block: int) -> np.ndarray:
    """Locality-preserving permutation of ``len(feats)`` rows: recursive
    max-variance median split of the margin space down to block-sized
    leaves.  Shared by ``build_bss`` and the append path
    (``repro.index.maintain``) so both lay rows out identically."""
    out: list[np.ndarray] = []

    def split(idx: np.ndarray):
        if len(idx) <= block:
            out.append(idx)
            return
        sub = feats[idx]
        dimm = int(np.argmax(sub.var(axis=0)))
        order = np.argsort(sub[:, dimm], kind="stable")
        half = len(idx) // 2
        split(idx[order[:half]])
        split(idx[order[half:]])

    split(np.arange(len(feats), dtype=np.int64))
    return np.concatenate(out)


def _pack_blocks(
    data_rows: np.ndarray, x: np.ndarray, y: np.ndarray, block: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad already-permuted engine-space rows to whole blocks and compute
    the per (block × plane) bounding boxes — the packing half of
    ``build_bss``, shared with the append path so appended blocks are
    bit-identical to built ones.  Returns ``(data_pad, valid, boxes)`` with
    ``boxes`` planes-major ``(4, M, n_blocks)``; an all-padding slot set
    yields ``EMPTY_BOX`` (min=+big, max=-big: bound +inf)."""
    n, m = x.shape
    n_blocks = math.ceil(n / block)
    pad = n_blocks * block - n
    valid = np.concatenate(
        [np.ones(n, dtype=bool), np.zeros(pad, dtype=bool)]
    )
    data_pad = np.concatenate(
        [data_rows, np.zeros((pad, data_rows.shape[1]), np.float32)]
    )
    xs = np.concatenate([x, np.zeros((pad, m), np.float32)])
    ys = np.concatenate([y, np.zeros((pad, m), np.float32)])
    xs = xs.reshape(n_blocks, block, m)
    ys = ys.reshape(n_blocks, block, m)
    vmask = valid.reshape(n_blocks, block, 1)
    x_lo, x_hi, y_lo, y_hi = (np.float32(v) for v in EMPTY_BOX)
    boxes = np.stack(
        [
            np.where(vmask, xs, x_lo).min(axis=1),
            np.where(vmask, xs, x_hi).max(axis=1),
            np.where(vmask, ys, y_lo).min(axis=1),
            np.where(vmask, ys, y_hi).max(axis=1),
        ],
    ).astype(np.float32)  # (4, n_blocks, M)
    return data_pad, valid, np.ascontiguousarray(boxes.transpose(0, 2, 1))


def build_bss(
    metric_name: str,
    data: np.ndarray,
    n_pivots: int = 16,
    n_pairs: int = 24,
    block: int = 128,
    seed: int = 0,
    mesh: Mesh | None = None,
) -> BSSIndex:
    """Build the blocked index (module docstring).  With ``mesh`` the device
    mirror is born sharded over the mesh's data axes and the batched query
    paths serve through the sharded engine (``repro.parallel.shard_index``);
    the host arrays and the numpy oracle are unaffected."""
    metric = get_metric(metric_name)  # validates; registers power names
    if not metric.four_point:
        raise ValueError(
            f"{metric_name!r} lacks the four-point property — planar "
            f"exclusion would be unsound.  Use a supermetric, or its "
            f"power transform (e.g. {metric_name}^0.5, paper §2.2)."
        )
    data = np.asarray(data, np.float32)
    if metric_name == "cosine":
        # Corpus onto the unit sphere once: supermetric cosine distance IS
        # l2 there, so the whole engine (projection, kernels, exact phase)
        # runs the l2 path with zero approximation.
        norms = np.linalg.norm(data, axis=1, keepdims=True)
        data = data / np.maximum(norms, _MIN_NORM)
    return _build_engine_index(
        metric_name, data, n_pivots=n_pivots, n_pairs=n_pairs, block=block,
        seed=seed, mesh=mesh,
    )


def _build_engine_index(
    metric_name: str,
    data: np.ndarray,
    *,
    n_pivots: int,
    n_pairs: int,
    block: int,
    seed: int,
    mesh: Mesh | None,
) -> BSSIndex:
    """``build_bss`` body over ENGINE-SPACE rows (already f32, already on
    the unit sphere for cosine).  Split out so ``repro.index.maintain``'s
    compact can rebuild from an index's stored rows with the EXACT ops of a
    fresh build — stored cosine rows are normalised once at original build,
    and renormalising them is not bit-stable."""
    rng = np.random.default_rng(seed)
    build_metric = _engine_metric(metric_name)
    n = data.shape[0]
    piv_idx = select_fft(build_metric, data, n_pivots, rng)
    pivots = data[piv_idx]

    # All pivot pairs, keep the M most separated (wide baselines give the
    # best-conditioned planes; beyond that the paper shows insensitivity).
    pd = pairwise_np(build_metric, pivots, pivots)
    cand = [(pd[i, j], i, j) for i in range(n_pivots) for j in range(i + 1, n_pivots)]
    cand.sort(reverse=True)
    m = min(n_pairs, len(cand))
    pairs = np.array([[i, j] for _, i, j in cand[:m]], dtype=np.int32)
    deltas = np.array([d for d, _, _ in cand[:m]], dtype=np.float32)

    dp = pairwise_np(build_metric, data, pivots).astype(np.float32)  # (n, P)
    x, y = _project_all(dp, pairs, deltas)  # (n, M) each
    feats = np.concatenate([x, y], axis=1)  # (n, 2M) margin space

    # locality-preserving permutation + MXU-aligned packing (helpers shared
    # with the append path, which runs them over new rows only)
    perm = _split_perm(feats, block)
    dsorted, valid, boxes = _pack_blocks(data[perm], x[perm], y[perm], block)
    pad = valid.shape[0] - n
    perm_pad = np.concatenate([perm, np.full(pad, -1, dtype=np.int64)])

    return BSSIndex(
        metric_name=metric_name,
        data=dsorted,
        perm=perm_pad,
        valid=valid,
        pivots=np.asarray(pivots, np.float32),
        pairs=pairs,
        deltas=deltas,
        boxes=boxes,
        block=block,
        seed=seed,
        next_id=n,
        mesh=mesh,
    )


@partial(jax.jit, static_argnames=("metric_name",))
def _lower_bounds_jit(
    metric_name: str,
    queries: jnp.ndarray,
    pivots: jnp.ndarray,
    pairs: jnp.ndarray,
    deltas: jnp.ndarray,
    boxes: jnp.ndarray,
) -> jnp.ndarray:
    """(Q, n_blocks) sound lower bound on d(q, any point in block).

    Thin jit wrapper over the shared bound math in ``_fused_lower_bounds``
    (jnp branch) — one definition serves the oracle, the stats helpers and
    the fused engine alike."""
    return _fused_lower_bounds(
        metric_name, queries, pivots, pairs, deltas, boxes,
        backend="jnp", bq=_DEFAULT_BQ, interpret=None,
    )


def bss_lower_bounds(index: BSSIndex, queries: np.ndarray) -> np.ndarray:
    queries = _engine_queries(index.metric_name, np.asarray(queries, np.float32))
    return np.asarray(
        _lower_bounds_jit(
            _engine_metric(index.metric_name),
            jnp.asarray(queries),
            jnp.asarray(index.pivots),
            jnp.asarray(index.pairs),
            jnp.asarray(index.deltas),
            jnp.asarray(index.boxes),
        )
    )


def _valid_per_block(index: BSSIndex) -> np.ndarray:
    """(n_blocks,) number of REAL corpus points per block.  The final block
    of a corpus whose size is not a multiple of ``block`` is partially
    padding; distance accounting must count only the valid slots."""
    return index.valid.reshape(index.n_blocks, index.block).sum(axis=1)


def _exact_counts(index: BSSIndex, alive: np.ndarray) -> np.ndarray:
    """(Q,) exact distance evaluations implied by a (Q, n_blocks) survival
    matrix — per-block VALID counts, not ``survived * block`` (which would
    count padded slots as distance evaluations and inflate the paper's
    figure of merit)."""
    return alive.astype(np.int64) @ _valid_per_block(index)


def _per_query_t(t, nq: int) -> np.ndarray:
    """Range thresholds as a (Q,) float32 vector: a scalar ``t`` broadcasts
    to every query; a vector carries PER-QUERY radii (the serving front
    mixes thresholds inside one micro-batch this way, and marks its padding
    rows with a negative radius — the planar bound is >= 0, so such a row
    survives no block, evaluates no distances and hits nothing)."""
    t_arr = np.asarray(t, np.float32)
    if t_arr.ndim == 0:
        return np.full(nq, float(t_arr), np.float32)
    if t_arr.shape != (nq,):
        raise ValueError(
            f"per-query t must have shape ({nq},), got {t_arr.shape}"
        )
    return t_arr


def bss_query(
    index: BSSIndex, queries: np.ndarray, t
) -> tuple[list[list[int]], dict]:
    """Exact range search — the NUMPY ORACLE path (see module docstring).

    ``t`` is a scalar threshold or a (Q,) vector of per-query radii.
    Returns per-query hit lists (original indices) and stats including the
    paper's figure of merit (distances/query: P pivot distances + the VALID
    points of each surviving block)."""
    queries = np.asarray(queries, np.float32)
    nq = queries.shape[0]
    t_vec = _per_query_t(t, nq)
    lb = bss_lower_bounds(index, queries)  # (Q, B)
    alive = lb <= t_vec[:, None]
    results: list[list[int]] = [[] for _ in range(nq)]
    bsz = index.block
    data = index.data
    # exact phase: per block, evaluate only the surviving queries
    for b in np.nonzero(alive.any(axis=0))[0]:
        qrows = np.nonzero(alive[:, b])[0]
        blk = data[b * bsz : (b + 1) * bsz]
        d = pairwise_np(index.metric_name, queries[qrows], blk)
        hits = d <= t_vec[qrows][:, None]
        for r, qi in enumerate(qrows):
            for off in np.nonzero(hits[r])[0]:
                orig = index.perm[b * bsz + off]
                if orig >= 0:
                    results[int(qi)].append(int(orig))
    n_pivots = index.pivots.shape[0]
    exact = _exact_counts(index, alive)  # padding-free, per query
    stats = {
        "pivot_dists_per_query": float(n_pivots),
        "exact_dists_per_query": float(exact.mean()),
        "dists_per_query": float(n_pivots + exact.mean()),
        "per_query_dists": n_pivots + exact,
        "block_exclusion_rate": float(1.0 - alive.mean()),
        "n_blocks": int(index.n_blocks),
        "generation": int(index.generation),
    }
    return results, stats


# ---------------------------------------------------------------------------
# Fused batched engine
# ---------------------------------------------------------------------------


# shared with the device forest walker — see repro.core.backends
_tile_survival = tile_survival
_resolve_backend = resolve_backend


def _fused_lower_bounds(
    metric_name: str,
    queries: jnp.ndarray,
    dev_pivots: jnp.ndarray,
    dev_pairs: jnp.ndarray,
    dev_deltas: jnp.ndarray,
    dev_boxes: jnp.ndarray,
    *,
    backend: str,
    bq: int,
    interpret: bool | None,
) -> jnp.ndarray:
    """(Q, B) planar lower bounds, through the Pallas kernels or pure jnp.

    ``metric_name`` is the ENGINE metric (cosine arrives here as l2 over
    pre-normalised queries).  Metrics with a registered tile kernel compute
    the query→pivot distances through it on the pallas backend; the rest
    (power transforms) use their jnp pairwise."""
    if backend == "pallas" and metric_name in KERNEL_METRICS:
        dqp = pairwise_kernel_call(
            metric_name, queries, dev_pivots, interpret=interpret
        )
    else:
        dqp = get_metric(metric_name).pairwise(queries, dev_pivots)  # (Q, P)
    d1 = dqp[:, dev_pairs[:, 0]]
    d2 = dqp[:, dev_pairs[:, 1]]
    if backend == "pallas":
        return planar_lower_bound_kernel_call(
            d1, d2, dev_deltas, dev_boxes, bq=bq, interpret=interpret
        )
    qx, qy = projection.project(d1, d2, dev_deltas[None, :])  # (Q, M)
    # (Q, M, 1) vs planes-major boxes (4, M, B) -> per-plane bound (Q, M, B),
    # max over planes.
    lb = projection.point_to_box(qx[:, :, None], qy[:, :, None], dev_boxes)
    return jnp.max(lb, axis=1)  # (Q, B)


def _masked_exact_dists(
    metric_name: str,
    queries: jnp.ndarray,
    dev_data: jnp.ndarray,
    dev_valid: jnp.ndarray,
    tile_mask: jnp.ndarray,
    *,
    backend: str,
    block: int,
    bq: int,
    interpret: bool | None,
) -> jnp.ndarray:
    """(Q, n_pad) exact distances for surviving (query-tile × block) cells;
    +inf everywhere the mask (or padding) excluded.

    On the pallas backend every metric with a registered tile kernel
    (l2 / jsd / triangular; cosine arrives as l2) runs the masked kernel —
    excluded tiles are skipped on the hardware, not computed-then-masked.
    The dense jnp fallback below serves only kernel-less metrics (power
    transforms) and the dense-survivor regime of the jnp backend; the
    sparse-survivor regime uses the cell-gather realisations
    (``_cells_exact_jit`` for range, ``_knn_round_cells_jit`` for kNN)."""
    if backend == "pallas" and metric_name in KERNEL_METRICS:
        dist = masked_pairwise_kernel_call(
            metric_name, queries, dev_data, tile_mask,
            bm=bq, bn=block, interpret=interpret,
        )
    else:
        # Same masked semantics through XLA: dense metric distances with the
        # survival mask applied.
        dense = get_metric(metric_name).pairwise(queries, dev_data)  # (Q, n_pad)
        mrep = jnp.repeat(
            jnp.repeat(tile_mask, bq, axis=0)[: queries.shape[0]],
            block,
            axis=1,
        )[:, : dev_data.shape[0]]
        dist = jnp.where(mrep, dense, jnp.inf)
    return jnp.where(dev_valid[None, :], dist, jnp.inf)


def _gather_cell_dists(
    metric_name: str,
    queries: jnp.ndarray,
    data: jnp.ndarray,
    valid: jnp.ndarray,
    qidx: jnp.ndarray,
    bidx: jnp.ndarray,
    block: int,
):
    """Shared cell-gather distance block for the sparse range AND kNN
    realisations: evaluate the metric only for the C gathered (query, block)
    cells.  Returns (d (C, block), pvalid (C, block))."""
    dim = data.shape[-1]
    blocks = data.reshape(-1, block, dim)
    gathered = blocks[bidx]  # (C, block, dim)
    qs = queries[qidx]  # (C, dim)
    metric = get_metric(metric_name)
    d = jax.vmap(lambda a, b: metric.pairwise(a[None], b)[0])(qs, gathered)
    pvalid = valid.reshape(-1, block)[bidx]  # (C, block)
    return d, pvalid


@partial(jax.jit, static_argnames=("metric_name", "block", "cap"))
def _cells_exact_jit(
    metric_name: str,
    queries: jnp.ndarray,
    data: jnp.ndarray,
    valid: jnp.ndarray,
    qidx: jnp.ndarray,
    bidx: jnp.ndarray,
    cell_valid: jnp.ndarray,
    t: jnp.ndarray,
    *,
    block: int,
    cap: int,
):
    """Exact phase over an explicit alive-cell list — the XLA realisation of
    the masked Pallas kernel's tile skipping: only the C surviving
    (query, block) cells are gathered and evaluated, and hits leave the
    device as a fixed-capacity compact list instead of a dense (Q, N)
    matrix.  ``t`` is the (Q,) per-query radius vector (each cell tests
    against its own query's radius).  Returns (hit_q (cap,), hit_pos (cap,),
    n_hits); entries past n_hits are -1.  Row-major over (cell, offset) with
    cells sorted by (query, block), so per-query hits come out in ascending
    position order — the oracle's order."""
    d, pvalid = _gather_cell_dists(
        metric_name, queries, data, valid, qidx, bidx, block
    )
    hit = (d <= t[qidx][:, None]) & pvalid & cell_valid[:, None]
    flat = hit.reshape(-1)
    n_hits = jnp.sum(flat)
    (pos,) = jnp.nonzero(flat, size=cap, fill_value=-1)
    cell = pos // block
    off = pos % block
    hit_q = jnp.where(pos >= 0, qidx[cell], -1)
    hit_pos = jnp.where(pos >= 0, bidx[cell] * block + off, -1)
    return hit_q, hit_pos, n_hits


def _next_pow2(x: int, lo: int = 16) -> int:
    return max(lo, 1 << (max(x, 1) - 1).bit_length())


# Above this alive-cell fraction the jnp backend computes the dense distance
# matrix (one big GEMM beats ragged gathers); below it, only the surviving
# cells are gathered.  Empirically ~0.08 on CPU; either branch is exact.
_DENSE_ALIVE_FRAC = 0.08


@partial(jax.jit, static_argnames=("metric_name", "block"))
def _dense_hit_mask_jit(
    metric_name: str,
    queries: jnp.ndarray,
    data: jnp.ndarray,
    valid: jnp.ndarray,
    alive: jnp.ndarray,
    t: jnp.ndarray,
    *,
    block: int,
):
    """Dense exact pass returning the (Q, N) hit BITMASK.

    One big GEMM with the hit test fused into its output traversal — for l2
    the test runs in the squared domain rearranged as
    ``|p|^2 - 2 q.p <= t^2 - |q|^2`` (no sqrt, and the f32 distance matrix
    itself is never materialised as an output) — masked by the per-query
    block survival.  ``t`` is the (Q,) per-query radius vector (a negative
    entry, e.g. a serving-front padding row, hits nothing).  Bools are 4x
    cheaper than the distances to move, and position extraction is a single
    host ``np.nonzero`` over the mask.  This serves the jnp backend, off the
    chip; on a TPU the fp32 Pallas path compacts its hits on the device
    instead, in two levels (:func:`_compact_hits`), and never runs one
    sized ``nonzero`` over Q x N."""
    nq = queries.shape[0]
    if metric_name == "l2":
        qf = queries.astype(jnp.float32)
        df = data.astype(jnp.float32)
        s = -2.0 * _dot_t(qf, df) + jnp.sum(df * df, axis=-1)[None, :]
        # t < 0 must hit nothing even though t*t > 0: send its threshold
        # to -inf (the squared-domain rearrangement is sign-blind).
        thresh = jnp.where(
            t >= 0, t * t - jnp.sum(qf * qf, axis=-1), -jnp.inf
        )  # (Q,)
        raw_hit = s <= thresh[:, None]
    else:
        raw_hit = get_metric(metric_name).pairwise(queries, data) <= t[:, None]
    hit = (
        raw_hit.reshape(nq, -1, block)
        & alive[:, :, None]
        & valid.reshape(1, -1, block)
    )
    return hit.reshape(nq, -1)


# Hit slots of one fused range call: 256 per padded query row, and never
# fewer than 32,768, because one real row of a small bucket can hold all of
# the batch's hits (sift-128 at its 1e-4 radius: 6,709 in one query).  A
# call with more hits than slots selects from its dense distances instead.
_COMPACT_CAP_FLOOR = 32_768
_COMPACT_CAP_PER_ROW = 256
# Hit slots filled per step of the compaction loop, which runs only as many
# steps as the call's hits need.
_COMPACT_CHUNK = 4096
# Lane width of the cumulative-count rows the compaction searches.
_LANES = 128


def _compact_cap(nq: int) -> int:
    """Hit slots of a fused range call over ``nq`` padded query rows."""
    return max(_COMPACT_CAP_FLOOR, _COMPACT_CAP_PER_ROW * nq)


def _running_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum along the last axis of a (rows, w) array of
    small non-negative ints, as a matmul with a triangular 0/1 matrix:
    exact in float32 while a row sums below 2**24.  For a v5e, a cumsum
    along 128 lanes or over a whole batch's cells compiles in seconds;
    this compiles in a fraction of one."""
    w = x.shape[-1]
    upper = (jnp.arange(w)[:, None] <= jnp.arange(w)[None, :]).astype(
        jnp.float32)
    return jnp.dot(x.astype(jnp.float32), upper,
                   precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)


def _compact_hits(dist: jnp.ndarray, t: jnp.ndarray, *, block: int,
                  cap: int):
    """Compact the hits ``dist <= t[:, None]`` of a (Q, n_pad) distance
    matrix.  Returns (counts (Q,) int32, pos (cap,) int32): each query's
    hit count, and the hit positions in row-major (query, position) order
    — the oracle's order — with -1 in the unused tail.  When the hits
    outnumber ``cap``, ``pos`` is all -1 and the caller selects from
    ``dist`` itself.

    Two levels, so no search ever runs over Q x n_pad: per (query, block)
    cell a hit count and their running sum ``cs``, laid out 128 cells to a
    row; then, for each hit slot k, the cell holding it is the number of
    running sums <= k (a compare against the last sum of each row, then
    within the one row it lands in), and its lane within the cell is found
    from that cell's own ``block`` distances.  Slots are filled
    ``_COMPACT_CHUNK`` at a time, for as many steps as there are hits."""
    nq, n_pad = dist.shape
    nb = n_pad // block
    n_cells = nq * nb
    # summed over a (Q/8, 8, B, block) view where Q allows: that view is
    # the TPU's own (8, 128) tiling of the matrix, so it is not copied
    sub = 8 if nq % 8 == 0 else 1
    cnt = jnp.sum(
        (dist <= t[:, None]).reshape(nq // sub, sub, nb, block), axis=3,
        dtype=jnp.int32,
    ).reshape(nq, nb)
    counts = jnp.sum(cnt, axis=1, dtype=jnp.int32)
    # the padding cells count 0, so the padded tail repeats the total
    n_rows = -(-n_cells // _LANES)
    in_row = _running_sum(jnp.pad(
        cnt.reshape(-1), (0, n_rows * _LANES - n_cells)
    ).reshape(n_rows, _LANES))
    row_last = jnp.cumsum(in_row[:, -1], dtype=jnp.int32)
    cs_rows = in_row + (row_last - in_row[:, -1])[:, None]
    total = row_last[-1]
    lanes = jnp.arange(_LANES, dtype=jnp.int32)
    chunk = _COMPACT_CHUNK
    width = -(-cap // chunk) * chunk

    def fill(state):
        k0, pos = state
        k = k0 + jnp.arange(chunk, dtype=jnp.int32)  # hit slots
        # cell r = #{i : cs[i] <= k}; below `total` it lies in row `sr`
        sr = jnp.minimum(
            jnp.sum(row_last[None, :] <= k[:, None], axis=1,
                    dtype=jnp.int32),
            n_rows - 1,
        )
        row = cs_rows[sr]  # (chunk, 128)
        c = jnp.minimum(
            jnp.sum(row <= k[:, None], axis=1, dtype=jnp.int32), _LANES - 1
        )
        cs_r = jnp.sum(jnp.where(lanes[None, :] == c[:, None], row, 0),
                       axis=1)
        r = jnp.minimum(sr * _LANES + c, n_cells - 1)
        q, b = r // nb, r % nb
        cell = jax.vmap(
            lambda qi, bi: jax.lax.dynamic_slice(
                dist, (qi, bi * block), (1, block))[0]
        )(q, b)
        run = _running_sum(cell <= t[q][:, None])  # (chunk, block)
        rank = k - (cs_r - run[:, -1])  # the slot's rank inside its cell
        lane = jnp.sum(run <= rank[:, None], axis=1, dtype=jnp.int32)
        p = jnp.where(k < total, b * block + lane, -1)
        return k0 + chunk, jax.lax.dynamic_update_slice(pos, p, (k0,))

    _, pos = jax.lax.while_loop(
        lambda s: (s[0] < total) & (total <= cap),
        fill,
        (jnp.int32(0), jnp.full(width, -1, jnp.int32)),
    )
    return counts, pos[:cap]


@partial(
    jax.jit,
    static_argnames=("metric_name", "block", "bq", "backend", "interpret",
                     "cap"),
)
def _query_batched_jit(
    metric_name: str,
    queries: jnp.ndarray,
    t: jnp.ndarray,
    dev: BSSDeviceArrays,
    *,
    block: int,
    bq: int,
    backend: str,
    interpret: bool | None,
    cap: int,
):
    """One fused range-search pass with its hits compacted on the device.
    Returns (counts (Q,), pos (cap,), alive (Q, B), tile_mask (Qtiles, B),
    dist (Q, n_pad)); ``counts`` and ``pos`` are :func:`_compact_hits`'s.

    ``t`` is the (Q,) per-query radius vector.  dist is +inf wherever the
    planar bound excluded the cell (or padding); every finite entry is an
    exact metric distance.  Exactness: a tile survives when ANY of its
    queries has lb <= its own t, so no true hit of any query is ever pruned
    (per-query hits are re-filtered by d <= t).  The caller reads ``dist``
    only when the hits outnumber ``cap``."""
    lb = _fused_lower_bounds(
        metric_name, queries, dev.pivots, dev.pairs, dev.deltas, dev.boxes,
        backend=backend, bq=bq, interpret=interpret,
    )  # (Q, B)
    alive = lb <= t[:, None]
    tile_mask = _tile_survival(alive, bq)  # (Qtiles, B)
    dist = _masked_exact_dists(
        metric_name, queries, dev.data, dev.valid, tile_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )
    counts, pos = _compact_hits(dist, t, block=block, cap=cap)
    return counts, pos, alive, tile_mask, dist


@partial(
    jax.jit,
    static_argnames=("metric_name", "block", "bq", "backend", "interpret"),
)
def _query_batched_bf16_jit(
    metric_name: str,
    queries: jnp.ndarray,
    t: jnp.ndarray,
    dev: BSSDeviceArrays,
    data16: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    block: int,
    bq: int,
    backend: str,
    interpret: bool | None,
):
    """One fused bf16 range pass with fp32 boundary re-check.

    The bound phase is UNTOUCHED (fp32 reference tables), so ``alive`` /
    ``tile_mask`` — and with them the paper's distance counts — are
    bit-identical to the fp32 engine's.  The scan streams the bf16 corpus
    (fp32 accumulation inside the kernels); with ``eps`` the derived margin
    (``repro.core.precision``):

      * ``d16 <= t - eps``  — SURE hit, no fp32 needed (margin soundness);
      * ``t - eps < d16 <= t + eps`` — boundary band: the fp32 corpus is
        re-scanned ONLY for tiles containing a band cell, through the same
        masked-kernel machinery, so every consulted fp32 value is the very
        value the fp32 engine computes — the final hit set is bit-identical;
      * everything else — sure miss (no true hit can have d16 > t + eps).

    Returns (hit (Q, n_pad) bool, alive (Q, B), tile_mask, recheck_tiles
    scalar, band_counts (Q,) int32)."""
    lb = _fused_lower_bounds(
        metric_name, queries, dev.pivots, dev.pairs, dev.deltas, dev.boxes,
        backend=backend, bq=bq, interpret=interpret,
    )
    alive = lb <= t[:, None]
    tile_mask = _tile_survival(alive, bq)
    d16 = _masked_exact_dists(
        metric_name, queries, data16, dev.valid, tile_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )
    t_col = t[:, None]
    sure = d16 <= t_col - eps
    band = (d16 <= t_col + eps) & ~sure
    band_blocks = band.reshape(queries.shape[0], -1, block).any(axis=2)
    recheck_mask = _tile_survival(band_blocks, bq) & tile_mask
    d32 = _masked_exact_dists(
        metric_name, queries, dev.data, dev.valid, recheck_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )
    hit = sure | (band & (d32 <= t_col))
    return (
        hit, alive, tile_mask, jnp.sum(recheck_mask),
        jnp.sum(band, axis=1, dtype=jnp.int32),
    )


@partial(jax.jit, static_argnames=("metric_name", "block", "cap"))
def _cells_exact_bf16_jit(
    metric_name: str,
    queries: jnp.ndarray,
    data16: jnp.ndarray,
    valid: jnp.ndarray,
    qidx: jnp.ndarray,
    bidx: jnp.ndarray,
    cell_valid: jnp.ndarray,
    t: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    block: int,
    cap: int,
):
    """Sparse (cell-gather) realisation of the bf16 range phase: like
    ``_cells_exact_jit`` but over the bf16 corpus.  Emits a compact list of
    the hits from cells with NO boundary-band point (``d16 <= t - eps``
    everywhere it fires — final by margin soundness) plus per-cell band
    flags: cells holding any ``t - eps < d16 <= t + eps`` point go back
    through the fp32 ``_cells_exact_jit`` (same gather shapes as the fp32
    engine, so every re-checked value is bit-identical to what that engine
    computes, and within a band cell its hit mask IS the fp32 engine's).
    Returns (hit_q, hit_pos, n_hits, band_cell (C,), band_counts (Q,))."""
    d, pvalid = _gather_cell_dists(
        metric_name, queries, data16, valid, qidx, bidx, block
    )
    ok = pvalid & cell_valid[:, None]
    tq = t[qidx][:, None]
    sure = (d <= tq - eps) & ok
    band = (d <= tq + eps) & ok & ~sure
    band_cell = band.any(axis=1)  # (C,)
    flat = (sure & ~band_cell[:, None]).reshape(-1)
    n_hits = jnp.sum(flat)
    (pos,) = jnp.nonzero(flat, size=cap, fill_value=-1)
    cell = pos // block
    off = pos % block
    hit_q = jnp.where(pos >= 0, qidx[cell], -1)
    hit_pos = jnp.where(pos >= 0, bidx[cell] * block + off, -1)
    nq = queries.shape[0]
    band_counts = jnp.zeros(nq, jnp.int32).at[
        jnp.clip(qidx, 0, nq - 1)
    ].add(jnp.sum(band, axis=1, dtype=jnp.int32))
    return hit_q, hit_pos, n_hits, band_cell, band_counts


def _batched_stats(index: BSSIndex, alive: np.ndarray, tile_mask: np.ndarray) -> dict:
    """The paper's figure of merit for a fused pass.  ``alive`` counts each
    query's own surviving blocks (the oracle's accounting, comparable across
    engines) weighted by per-block VALID point counts — padded slots are
    never counted as distance evaluations; ``tiles_computed`` counts what
    the hardware actually ran (tile-level OR over the query tile)."""
    n_pivots = index.pivots.shape[0]
    exact = _exact_counts(index, alive)
    mean_exact = float(exact.mean()) if exact.size else 0.0
    return {
        "pivot_dists_per_query": float(n_pivots),
        "exact_dists_per_query": mean_exact,
        "dists_per_query": float(n_pivots) + mean_exact,
        # per-request accounting for the serving front: each query's OWN
        # charge (pivot distances + its surviving blocks' valid points)
        "per_query_dists": n_pivots + exact,
        "block_exclusion_rate": float(1.0 - alive.mean()) if alive.size else 1.0,
        "tiles_computed": int(tile_mask.sum()),
        "tile_exclusion_rate": (
            float(1.0 - tile_mask.mean()) if tile_mask.size else 1.0
        ),
        "n_blocks": int(index.n_blocks),
        "generation": int(index.generation),
        # per-mechanism attribution (repro.obs.schema): every block BSS
        # excludes is excluded by the planar four-point bound — the Hilbert
        # mechanism — read off the engine's functional `alive` output
        "excluded": {
            "hilbert": (
                index.n_blocks - alive.sum(axis=1)
            ).astype(np.int64),
        },
    }


def _finish_stats(stats: dict, *, kind: str, backend: str,
                  engine: str = "bss") -> dict:
    """Stamp the shared observability schema onto an engine stats dict at
    the jit boundary (see ``repro.obs.schema`` for the contract)."""
    return obs_schema.normalise_stats(
        stats, engine=engine, kind=kind, backend=backend,
        n_queries=int(np.asarray(stats["per_query_dists"]).shape[0]),
        excluded=stats.get("excluded"),
    )


def _jit_cache_sizes() -> dict:
    return {name: jit_cache_size(fn) for name, fn in ENGINE_JITS.items()}


class _CallRecord:
    """The host side of one engine call: its ``engine/<kind>/*`` spans
    (``repro.obs.spans.SpanLog``), the bytes it copies from the device and
    the compiles it causes.  Every device-to-host copy of the call goes
    through :meth:`fetch`.  :meth:`finish` puts the three in ``stats`` as
    ``spans``, ``d2h_bytes`` and ``compiles``."""

    __slots__ = ("log", "d2h_bytes", "_device", "_d2h", "_sizes")

    def __init__(self, kind: str):
        self.log = SpanLog()
        self.d2h_bytes = 0
        self._device = f"engine/{kind}/device"
        self._d2h = f"engine/{kind}/d2h"
        self._sizes = _jit_cache_sizes()

    def fetch(self, *arrays):
        """Copy device arrays to numpy, counting their bytes: first wait
        for the device to produce them (``engine/<kind>/device``), so the
        copy's own span (``engine/<kind>/d2h``) holds no device time."""
        with self.log.span(self._device):
            jax.block_until_ready(arrays)
        with self.log.span(self._d2h):
            out = [np.asarray(a) for a in arrays]
        self.d2h_bytes += sum(a.nbytes for a in out)
        return out[0] if len(out) == 1 else out

    def finish(self, stats: dict) -> dict:
        """``compiles`` names each engine jit that gained cache entries
        during the call (in this process: a concurrent call's compiles
        count too) with how many."""
        after = _jit_cache_sizes()
        stats["spans"] = self.log.records
        stats["d2h_bytes"] = int(self.d2h_bytes)
        stats["compiles"] = {
            name: after[name] - self._sizes[name] for name in after
            if self._sizes[name] >= 0 and after[name] > self._sizes[name]
        }
        return stats


def bss_query_batched(
    index: BSSIndex,
    queries: np.ndarray,
    t,
    *,
    opts: EngineOpts | None = None,
    bq: int | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    realisation: str | None = None,
    precision: str | None = None,
) -> tuple[list[list[int]], dict]:
    """Exact range search through the fused jitted engine.

    Engine options travel as one ``opts=EngineOpts(...)`` record
    (``repro.core.backends``); the per-knob kwargs are the legacy spelling,
    kept working through :func:`resolve_engine_opts` (they warn under
    ``REPRO_STRICT_API=1``).

    ``precision="bf16"`` streams the bfloat16 corpus mirror through the
    exact phase (half the corpus HBM traffic; fp32 accumulation unchanged)
    and re-checks the boundary band ``|d16 - t| <= eps`` against the fp32
    corpus — hits AND per-query distance counts stay bit-identical to the
    fp32 engine (margin derivation: ``repro.core.precision``).  Stats gain
    ``band_eps`` / ``recheck_tiles`` / ``per_query_recheck`` telemetry;
    the paper's figure of merit (``per_query_dists``) is charged exactly as
    in fp32 — re-checked points are reported separately, never double
    counted.

    ``t`` is a scalar threshold or a (Q,) vector of PER-QUERY radii — the
    serving front mixes thresholds inside one micro-batch this way; each
    query's survival, hits and distance accounting use only its own radius,
    so every row is exactly the single-threshold engine's row (a negative
    radius excludes its row from everything — the front's padding).

    ``realisation="dense"`` pins the jnp backend to the dense exact phase:
    the sparse cell-gather realisation pads its alive-cell count to a
    DATA-DEPENDENT power of two, so a latency-sensitive caller (the async
    serving front) would pay an unpredictable mid-stream recompile whenever
    traffic produces a fresh shape class — the dense pass's shapes are
    fixed by (Q, N) alone, keeping compile count bounded by the front's
    bucket ladder.  Either realisation is exact; "adaptive" (default)
    picks by survivor density as before.

    Bit-equal to the ``bss_query`` oracle's hit lists (same indices, same
    per-query order) whenever float32 and float64 agree on ``d <= t`` —
    which the test suite enforces at safe thresholds.

    The ``pallas`` backend runs the dense masked kernel (tile skipping on
    TPU, interpret mode in tests) and compacts its hits on the device
    (:func:`_compact_hits`); only a call with more hits than
    :func:`_compact_cap` slots copies the distance matrix and selects on
    the host (``stats["compact_overflow"]`` is then 1).  The ``jnp``
    backend picks its exact phase by survivor density: below
    ``_DENSE_ALIVE_FRAC`` only the alive (query, block) cells are gathered
    (``_cells_exact_jit``); above it one dense per-query-masked pass wins
    (``_dense_hit_mask_jit``).  Either way only compact hits / a bitmask
    cross back to the host — never the distance matrix.

    A mesh-built index (``build_bss(mesh=...)``) serves through the sharded
    engine — one shard-local fused pass per device, hit bitmasks
    concatenated back in corpus order; results and stats are identical."""
    opts = resolve_engine_opts(
        opts, bq=bq, backend=backend, interpret=interpret,
        realisation=realisation, precision=precision,
    )
    if index.mesh is not None:
        from repro.parallel.shard_index import sharded_query_batched

        return sharded_query_batched(index.sharded(), queries, t, opts=opts)
    bq = opts.bq if opts.bq is not None else _DEFAULT_BQ
    interpret = opts.interpret
    realisation = opts.realisation
    precision = opts.precision
    backend = _resolve_backend(opts.backend)
    metric_eng = _engine_metric(index.metric_name)
    queries = _engine_queries(index.metric_name, np.asarray(queries, np.float32))
    nq = queries.shape[0]
    call = _CallRecord("range")
    if nq == 0:
        stats = _batched_stats(
            index,
            np.zeros((0, index.n_blocks), bool),
            np.zeros((0, index.n_blocks), bool),
        )
        stats["precision"] = precision
        if precision == "bf16":
            _bf16_stats(stats, index.bf16_margin(), 0, np.zeros(0, np.int64))
        return [], call.finish(
            _finish_stats(stats, kind="range", backend=backend)
        )
    t_vec = _per_query_t(t, nq)
    dev = index.device
    if precision == "bf16":
        return _query_batched_bf16(
            index, metric_eng, queries, t_vec, dev, call,
            bq=bq, backend=backend, interpret=interpret,
            realisation=realisation,
        )
    if backend == "jnp":
        with call.log.span("engine/range/launch"):
            qj = jnp.asarray(queries)
            lb = _lower_bounds_jit(
                metric_eng, qj, dev.pivots, dev.pairs, dev.deltas, dev.boxes,
            )
        lb = call.fetch(lb)
        with call.log.span("engine/range/launch"):
            alive = lb <= t_vec[:, None]
            dense = realisation == "dense" or alive.mean() > _DENSE_ALIVE_FRAC
            if dense:
                mask = _dense_hit_mask_jit(
                    metric_eng, qj, dev.data, dev.valid,
                    jnp.asarray(alive), jnp.asarray(t_vec), block=index.block,
                )
            else:
                qidx, bidx = np.nonzero(alive)  # sorted by (query, block)
                c = len(qidx)
                c_pad = _next_pow2(c)
                cell_valid = jnp.asarray(np.arange(c_pad) < c)
                qidx_p = jnp.asarray(np.pad(qidx, (0, c_pad - c)), jnp.int32)
                bidx_p = jnp.asarray(np.pad(bidx, (0, c_pad - c)), jnp.int32)
                cap = _next_pow2(8 * max(nq, 1), lo=1024)
        if dense:
            mask = call.fetch(mask)
        else:
            while True:
                with call.log.span("engine/range/launch"):
                    outs = _cells_exact_jit(
                        metric_eng, qj, dev.data, dev.valid,
                        qidx_p, bidx_p, cell_valid, jnp.asarray(t_vec),
                        block=index.block, cap=cap,
                    )
                n_hits = int(call.fetch(outs[2]))
                if n_hits <= cap:
                    break
                cap = _next_pow2(n_hits)  # rare: recompile, bigger bucket
            hit_q, hit_pos = call.fetch(outs[0], outs[1])
        with call.log.span("engine/range/select"):
            if dense:
                hit_q, hit_pos = np.nonzero(mask)  # (query, position) ascending
            else:
                hit_q, hit_pos = hit_q[:n_hits], hit_pos[:n_hits]
            orig = index.perm[hit_pos]
            counts = np.bincount(hit_q, minlength=nq)
            per_query = np.split(orig, np.cumsum(counts)[:-1])
            results = [r.tolist() for r in per_query]
        with call.log.span("engine/range/stats"):
            tile_mask = call.fetch(_tile_survival(jnp.asarray(alive), bq))
            stats = _batched_stats(index, alive, tile_mask)
            stats["precision"] = "fp32"
        return results, call.finish(
            _finish_stats(stats, kind="range", backend=backend)
        )
    cap = _compact_cap(nq)
    with call.log.span("engine/range/launch"):
        *outs, dist = _query_batched_jit(
            metric_eng,
            jnp.asarray(queries),
            jnp.asarray(t_vec),
            dev,
            block=index.block,
            bq=bq,
            backend=backend,
            interpret=interpret,
            cap=cap,
        )
    counts, pos, alive, tile_mask = call.fetch(*outs)
    n_hits = int(counts.sum())
    overflow = n_hits > cap
    if overflow:  # more hits than slots: select from the dense distances
        dist = call.fetch(dist)
    with call.log.span("engine/range/select"):
        if overflow:
            # row-major: positions ascending within a query
            pidx = np.nonzero(dist <= t_vec[:, None])[1]
        else:
            pidx = pos[:n_hits]
        orig = index.perm[pidx]
        per_query = np.split(orig, np.cumsum(counts)[:-1])
        results = [r.tolist() for r in per_query]
    with call.log.span("engine/range/stats"):
        stats = _batched_stats(index, alive, tile_mask)
        stats["precision"] = "fp32"
        stats["compact_overflow"] = int(overflow)
    return results, call.finish(
        _finish_stats(stats, kind="range", backend=backend)
    )


def _bf16_stats(stats: dict, eps: float, recheck_tiles: int,
                per_query_recheck: np.ndarray) -> dict:
    """Augment an engine stats dict with the bf16 re-check telemetry.  The
    existing keys (the paper's figure of merit included) are bit-identical
    to the fp32 engine's; the re-check volume is reported SEPARATELY so the
    count-parity contract survives."""
    stats["precision"] = "bf16"
    stats["band_eps"] = float(eps)
    stats["recheck_tiles"] = int(recheck_tiles)
    stats["per_query_recheck"] = np.asarray(per_query_recheck, np.int64)
    stats["recheck_points_per_query"] = (
        float(stats["per_query_recheck"].mean())
        if stats["per_query_recheck"].size else 0.0
    )
    return stats


def _query_batched_bf16(
    index: BSSIndex,
    metric_eng: str,
    queries: np.ndarray,
    t_vec: np.ndarray,
    dev: BSSDeviceArrays,
    call: _CallRecord,
    *,
    bq: int,
    backend: str,
    interpret: bool | None,
    realisation: str,
) -> tuple[list[list[int]], dict]:
    """Host driver for the bf16 range phase (both realisations); see
    ``_query_batched_bf16_jit`` for the dense scheme and
    ``_cells_exact_bf16_jit`` for the sparse one."""
    nq = queries.shape[0]
    eps = index.bf16_margin()
    data16 = index.device_bf16
    qj = jnp.asarray(queries)
    eps_j = jnp.float32(eps)
    if backend == "jnp" and realisation != "dense":
        with call.log.span("engine/range/launch"):
            lb = _lower_bounds_jit(
                metric_eng, qj, dev.pivots, dev.pairs, dev.deltas, dev.boxes,
            )
        lb = call.fetch(lb)
        alive = lb <= t_vec[:, None]
        # Same adaptive branch condition as fp32 (it reads only the fp32
        # bound phase), so both precisions pick the same realisation.
        if alive.mean() <= _DENSE_ALIVE_FRAC:
            with call.log.span("engine/range/launch"):
                qidx, bidx = np.nonzero(alive)  # sorted by (query, block)
                c = len(qidx)
                c_pad = _next_pow2(c)
                qidx_p = np.pad(qidx, (0, c_pad - c)).astype(np.int32)
                bidx_p = np.pad(bidx, (0, c_pad - c)).astype(np.int32)
                cell_valid = jnp.asarray(np.arange(c_pad) < c)
                tj = jnp.asarray(t_vec)
                cap = _next_pow2(8 * max(nq, 1), lo=1024)
            while True:
                with call.log.span("engine/range/launch"):
                    outs = _cells_exact_bf16_jit(
                        metric_eng, qj, data16, dev.valid,
                        jnp.asarray(qidx_p), jnp.asarray(bidx_p),
                        cell_valid, tj, eps_j,
                        block=index.block, cap=cap,
                    )
                n_hits = int(call.fetch(outs[2]))
                if n_hits <= cap:
                    break
                cap = _next_pow2(n_hits)
            hit_q, hit_pos, band_cell, band_counts = call.fetch(
                outs[0], outs[1], outs[3], outs[4]
            )
            hit_q, hit_pos = hit_q[:n_hits], hit_pos[:n_hits]
            # fp32 re-check of every band CELL through the fp32 engine's own
            # sparse realisation — values and hit masks bit-identical to it.
            band_cells = np.nonzero(band_cell)[0]
            if band_cells.size:
                q2 = qidx_p[band_cells]
                b2 = bidx_p[band_cells]
                c2 = len(band_cells)
                c2_pad = _next_pow2(c2)
                cap2 = _next_pow2(8 * max(nq, 1), lo=1024)
                while True:
                    with call.log.span("engine/range/launch"):
                        outs = _cells_exact_jit(
                            metric_eng, qj, dev.data, dev.valid,
                            jnp.asarray(np.pad(q2, (0, c2_pad - c2)), jnp.int32),
                            jnp.asarray(np.pad(b2, (0, c2_pad - c2)), jnp.int32),
                            jnp.asarray(np.arange(c2_pad) < c2), tj,
                            block=index.block, cap=cap2,
                        )
                    n_r = int(call.fetch(outs[2]))
                    if n_r <= cap2:
                        break
                    cap2 = _next_pow2(n_r)
                rq, rp = call.fetch(outs[0], outs[1])
            with call.log.span("engine/range/select"):
                if band_cells.size:
                    hit_q = np.concatenate([hit_q, rq[:n_r]])
                    hit_pos = np.concatenate([hit_pos, rp[:n_r]])
                    order = np.lexsort((hit_pos, hit_q))
                    hit_q = hit_q[order]
                    hit_pos = hit_pos[order]
                orig = index.perm[hit_pos]
                counts = np.bincount(hit_q, minlength=nq)
                per_query = np.split(orig, np.cumsum(counts)[:-1])
                results = [r.tolist() for r in per_query]
            with call.log.span("engine/range/stats"):
                tile_mask = call.fetch(
                    _tile_survival(jnp.asarray(alive), bq)
                )
                stats = _batched_stats(index, alive, tile_mask)
                _bf16_stats(stats, eps, 0, band_counts)
            return results, call.finish(
                _finish_stats(stats, kind="range", backend=backend)
            )
    with call.log.span("engine/range/launch"):
        outs = _query_batched_bf16_jit(
            metric_eng, qj, jnp.asarray(t_vec), dev, data16, eps_j,
            block=index.block, bq=bq, backend=backend, interpret=interpret,
        )
    hit, alive, tile_mask, recheck_tiles, band_counts = call.fetch(*outs)
    with call.log.span("engine/range/select"):
        hit_q, hit_pos = np.nonzero(hit)  # row-major: positions ascending
        orig = index.perm[hit_pos]
        counts = hit.sum(axis=1)
        per_query = np.split(orig, np.cumsum(counts)[:-1])
        results = [r.tolist() for r in per_query]
    with call.log.span("engine/range/stats"):
        stats = _batched_stats(index, alive, tile_mask)
        _bf16_stats(stats, eps, int(recheck_tiles), band_counts)
    return results, call.finish(
        _finish_stats(stats, kind="range", backend=backend)
    )


@partial(
    jax.jit,
    static_argnames=("metric_name", "block", "bq", "k", "backend", "interpret"),
)
def _knn_round_jit(
    metric_name: str,
    queries: jnp.ndarray,
    radii: jnp.ndarray,
    lb: jnp.ndarray,
    dev: BSSDeviceArrays,
    *,
    k: int,
    block: int,
    bq: int,
    backend: str,
    interpret: bool | None,
):
    """One batched radius-deepening round over ALL queries.

    ``lb`` is the radius-independent (Q, B) planar bound matrix, computed
    once by the caller and reused across rounds.  Returns (cand_idx (Q, k)
    positions in the permuted layout, cand_dist (Q, k) ascending, kth (Q,),
    done (Q,), alive (Q, B), tile_mask).

    ``done`` is sound: if the kth-smallest computed distance is <= the
    query's radius, every unevaluated point sits in a block whose planar
    lower bound exceeds the radius, hence is farther than the kth candidate
    — the top-k is final."""
    alive = lb <= radii[:, None]
    tile_mask = _tile_survival(alive, bq)
    dist = _masked_exact_dists(
        metric_name, queries, dev.data, dev.valid, tile_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )  # (Q, n_pad), +inf where pruned/padding
    neg, cand_idx = jax.lax.top_k(-dist, k)  # k smallest distances
    cand_dist = -neg  # ascending
    kth = cand_dist[:, -1]
    # done when nothing unevaluated can beat the kth candidate: either the
    # radius covers it, or every block was computed anyway.
    done = jnp.isfinite(kth) & ((kth <= radii) | jnp.all(alive, axis=1))
    return cand_idx, cand_dist, kth, done, alive, tile_mask


@partial(
    jax.jit,
    static_argnames=("metric_name", "block", "bq", "k", "backend", "interpret"),
)
def _knn_round_bf16_jit(
    metric_name: str,
    queries: jnp.ndarray,
    radii: jnp.ndarray,
    lb: jnp.ndarray,
    dev: BSSDeviceArrays,
    data16: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    k: int,
    block: int,
    bq: int,
    backend: str,
    interpret: bool | None,
):
    """One bf16 radius-deepening round with fp32 boundary re-check.

    The bf16 scan's own kth-smallest distance ``kth16`` bounds the fp32
    kth within ``eps`` (sorted order statistics of pointwise-eps-close
    vectors), so every member of the fp32 top-k satisfies
    ``d16 <= kth16 + 2*eps`` — that band is re-checked against the fp32
    corpus and the top-k re-taken over the fp32 values (+inf outside the
    band; everything excluded is strictly beyond the fp32 kth, ties at the
    kth included, so selection AND tie order match the fp32 round exactly).
    The ``isfinite`` guard keeps the band inside the computed tile set:
    when fewer than k cells are computed, ``kth16`` is +inf and the band is
    exactly the computed cells — again the fp32 round's pool.  Outputs are
    bit-identical to ``_knn_round_jit``, so the radius schedule (and with
    it the per-query distance counts) never diverges."""
    alive = lb <= radii[:, None]
    tile_mask = _tile_survival(alive, bq)
    d16 = _masked_exact_dists(
        metric_name, queries, data16, dev.valid, tile_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )
    neg16, _ = jax.lax.top_k(-d16, k)
    kth16 = -neg16[:, -1]
    bthr = jnp.where(jnp.isfinite(kth16), kth16 + 2.0 * eps, jnp.inf)
    band = (d16 <= bthr[:, None]) & jnp.isfinite(d16)
    band_blocks = band.reshape(queries.shape[0], -1, block).any(axis=2)
    recheck_mask = _tile_survival(band_blocks, bq) & tile_mask
    d32 = _masked_exact_dists(
        metric_name, queries, dev.data, dev.valid, recheck_mask,
        backend=backend, block=block, bq=bq, interpret=interpret,
    )
    dist = jnp.where(band, d32, jnp.inf)
    neg, cand_idx = jax.lax.top_k(-dist, k)
    cand_dist = -neg
    kth = cand_dist[:, -1]
    done = jnp.isfinite(kth) & ((kth <= radii) | jnp.all(alive, axis=1))
    return (
        cand_idx, cand_dist, kth, done, alive, tile_mask,
        jnp.sum(recheck_mask), jnp.sum(band, axis=1, dtype=jnp.int32),
    )


@partial(jax.jit, static_argnames=("metric_name", "k", "block"))
def _knn_round_cells_jit(
    metric_name: str,
    queries: jnp.ndarray,
    data: jnp.ndarray,
    valid: jnp.ndarray,
    qidx: jnp.ndarray,
    bidx: jnp.ndarray,
    cell_valid: jnp.ndarray,
    *,
    k: int,
    block: int,
):
    """Sparse kNN round: the cell-gather realisation of the masked kernel's
    tile skipping for the jnp backend.  Exact distances are evaluated ONLY
    for the C host-gathered alive (query, block) cells — O(C·block·dim)
    arithmetic instead of the dense O(Q·N·dim) — then scatter-min'd into a
    (Q, n_pad) +inf matrix for ``top_k``.  Padded cells carry +inf, so the
    min-scatter is a no-op for them regardless of scatter order.  Returns
    (cand_idx (Q, k) permuted positions, cand_dist (Q, k) ascending).

    The scatter target is still O(Q·n_pad) floats — same memory as the
    dense round, but 4-byte writes instead of dim-wide metric arithmetic
    (the win is ~dim× on compute, which is what dominates for jsd /
    triangular).  A survivor-proportional top-k (per-query capped gather)
    is the follow-up when kNN serving memory becomes the binding
    constraint — see ROADMAP."""
    d, pvalid = _gather_cell_dists(
        metric_name, queries, data, valid, qidx, bidx, block
    )
    d = jnp.where(pvalid & cell_valid[:, None], d, jnp.inf)
    nq = queries.shape[0]
    n_blocks = data.shape[0] // block
    dense = jnp.full((nq, n_blocks, block), jnp.inf, jnp.float32)
    dense = dense.at[qidx, bidx].min(d)
    neg, cand_idx = jax.lax.top_k(-dense.reshape(nq, -1), k)
    return cand_idx, -neg


@partial(jax.jit, static_argnames=("metric_name", "k", "block"))
def _knn_round_cells_bf16_jit(
    metric_name: str,
    queries: jnp.ndarray,
    data16: jnp.ndarray,
    valid: jnp.ndarray,
    qidx: jnp.ndarray,
    bidx: jnp.ndarray,
    cell_valid: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    k: int,
    block: int,
):
    """bf16 half of a sparse kNN round: gather the alive cells from the
    bf16 corpus, find each query's bf16 kth, and flag the (query, block)
    cells holding any point inside the re-check band
    ``d16 <= kth16 + 2*eps`` (containment argument in
    ``_knn_round_bf16_jit``).  The caller then runs the UNCHANGED fp32
    ``_knn_round_cells_jit`` over just those cells — identical gather
    shapes, so its candidate values, indices and tie order are exactly the
    fp32 round's.  Returns (band_cell (C,) bool, band_counts (Q,) int32)."""
    d, pvalid = _gather_cell_dists(
        metric_name, queries, data16, valid, qidx, bidx, block
    )
    d = jnp.where(pvalid & cell_valid[:, None], d, jnp.inf)
    nq = queries.shape[0]
    n_blocks = data16.shape[0] // block
    dense16 = jnp.full((nq, n_blocks, block), jnp.inf, jnp.float32)
    dense16 = dense16.at[qidx, bidx].min(d)
    neg16, _ = jax.lax.top_k(-dense16.reshape(nq, -1), k)
    kth16 = -neg16[:, -1]
    bthr = jnp.where(jnp.isfinite(kth16), kth16 + 2.0 * eps, jnp.inf)
    qi = jnp.clip(qidx, 0, nq - 1)
    band = (d <= bthr[qi][:, None]) & jnp.isfinite(d)  # (C, block)
    band_cell = band.any(axis=1)
    band_counts = jnp.zeros(nq, jnp.int32).at[qi].add(
        jnp.sum(band, axis=1, dtype=jnp.int32)
    )
    return band_cell, band_counts


@partial(jax.jit, static_argnames=("metric_name", "bq", "backend", "interpret"))
def _knn_lb_jit(
    metric_name: str,
    queries: jnp.ndarray,
    dev: BSSDeviceArrays,
    *,
    bq: int,
    backend: str,
    interpret: bool | None,
) -> jnp.ndarray:
    return _fused_lower_bounds(
        metric_name, queries, dev.pivots, dev.pairs, dev.deltas, dev.boxes,
        backend=backend, bq=bq, interpret=interpret,
    )


# Every jitted function of the engine, under the name its compiles are
# counted by: each call's ``stats["compiles"]`` and the serving front's
# ``compile/cache_size`` / ``compile/recompiles`` series read this one list.
ENGINE_JITS = {
    "range/lb": _lower_bounds_jit,
    "range/dense": _dense_hit_mask_jit,
    "range/cells": _cells_exact_jit,
    "range/fused": _query_batched_jit,
    "range/bf16": _query_batched_bf16_jit,
    "range/cells_bf16": _cells_exact_bf16_jit,
    "knn/lb": _knn_lb_jit,
    "knn/round": _knn_round_jit,
    "knn/round_bf16": _knn_round_bf16_jit,
    "knn/round_cells": _knn_round_cells_jit,
    "knn/round_cells_bf16": _knn_round_cells_bf16_jit,
}


def _knn_empty_stats(index: BSSIndex, nq: int, precision: str,
                     backend: str, engine: str = "bss") -> dict:
    """Schema-conformant stats for the kNN early returns (no queries, or
    an empty valid corpus): zero rounds, zero work."""
    stats = {
        "rounds": 0, "pivot_dists_per_query": 0.0,
        "exact_dists_per_query": 0.0, "dists_per_query": 0.0,
        "per_query_dists": np.zeros(nq, np.int64),
        "tiles_computed": 0, "n_blocks": int(index.n_blocks),
        "generation": int(index.generation),
        "precision": precision,
        "excluded": {"hilbert": np.zeros(nq, np.int64)},
    }
    if precision == "bf16":
        _bf16_stats(stats, index.bf16_margin(), 0, np.zeros(nq, np.int64))
    return _finish_stats(stats, kind="knn", backend=backend, engine=engine)


def bss_knn_batched(
    index: BSSIndex,
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
    realisation: str | None = None,
    precision: str | None = None,
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Exact batched kNN: the range-search reduction run as jitted
    radius-deepening rounds over all queries at once.

    Engine options travel as ``opts=EngineOpts(...)`` exactly as in
    ``bss_query_batched`` (legacy per-knob kwargs shimmed the same way);
    ``r0`` / ``growth`` / ``max_rounds`` are the radius SCHEDULE — kNN
    semantics, not engine plumbing — and stay explicit kwargs.

    ``precision="bf16"`` runs every round's scan over the bfloat16 corpus
    mirror and re-checks the per-round radius band
    ``d16 <= kth16 + 2*eps`` against the fp32 corpus
    (``_knn_round_bf16_jit``) — candidates, distances, the radius schedule
    and the per-query distance counts are bit-identical to the fp32 engine;
    stats gain the re-check telemetry (``band_eps`` / ``recheck_tiles`` /
    ``per_query_recheck``).

    ``realisation="dense"`` pins every jnp round to the dense masked pass
    (no sparse cell-gather): shapes depend only on (Q, N, k), so a serving
    front's compile count stays bounded by its bucket ladder — see
    ``bss_query_batched``.  Both realisations are exact; they may disagree
    in the last ulp of a distance, which can shift the radius schedule (and
    so the per-query distance COUNTS, never the results) — count-parity
    contracts should pin one realisation (the sharded engine and its tests
    pin dense).

    Round scheme (each round is ONE jitted call, fixed shapes, no recompiles):
      * every query carries its own radius; blocks with planar bound above it
        are excluded from the masked exact phase;
      * ``jax.lax.top_k`` extracts the k nearest computed candidates;
      * a query is finished when its kth candidate distance <= its radius
        (soundness argument in ``_knn_round_jit``);
      * unfinished queries tighten AND widen: the kth-nearest-so-far
        distance is an upper bound on the true kth distance, so the next
        radius is ``min(kth_so_far, widened)`` where ``widened`` is the
        per-query radius that doubles the number of surviving blocks (read
        off the query's sorted block bounds — scale-free, so convergence
        takes at most ~log2(n_blocks) rounds).  One extra round at radius
        ``kth_so_far`` is always sufficient; the min keeps the mask as
        tight as the current evidence allows.  After ``max_rounds`` any
        stragglers run one exhaustive round (radius = inf), so the result
        is always exact.

    The initial radius (when ``r0`` is None) is per-query and scale-free:
    the ceil(2k/block)-th smallest block bound — the smallest radius that
    could possibly admit 2k candidate points, by the bound's own ordering.

    On the jnp backend each round is adaptive in survivor density (mirroring
    the range path): sparse rounds gather only the alive (query, block)
    cells (``_knn_round_cells_jit``), dense rounds run the masked dense pass
    — either way the round's arithmetic is exact and the result identical.

    Returns (indices (Q, k) original ids sorted by ascending distance — -1
    when the corpus holds fewer than k valid points, distances (Q, k), stats).

    A mesh-built index (``build_bss(mesh=...)``) serves through the sharded
    engine: per-shard rounds merged by all-gather + global top-k under the
    same radius schedule — results and distance counts are identical.
    """
    opts = resolve_engine_opts(
        opts, bq=bq, backend=backend, interpret=interpret,
        realisation=realisation, precision=precision,
    )
    if index.mesh is not None:
        from repro.parallel.shard_index import sharded_knn_batched

        return sharded_knn_batched(
            index.sharded(), queries, k, r0=r0, growth=growth,
            max_rounds=max_rounds, opts=opts,
        )
    bq = opts.bq if opts.bq is not None else _DEFAULT_BQ
    interpret = opts.interpret
    realisation = opts.realisation
    precision = opts.precision
    backend = _resolve_backend(opts.backend)
    metric_eng = _engine_metric(index.metric_name)
    queries = _engine_queries(index.metric_name, np.asarray(queries, np.float32))
    nq = queries.shape[0]
    k = int(k)
    if k <= 0:
        raise ValueError(f"k must be positive, got {k}")
    call = _CallRecord("knn")
    if nq == 0:
        return (
            np.zeros((0, k), np.int64),
            np.zeros((0, k), np.float32),
            call.finish(_knn_empty_stats(index, 0, precision, backend)),
        )
    # clamp to the VALID corpus size: with k_run > n_valid the kth distance
    # would stay inf and no round could ever finish early
    k_run = min(k, index.n_valid)
    if k_run == 0:
        return (
            np.full((nq, k), -1, np.int64),
            np.full((nq, k), np.inf, np.float32),
            call.finish(_knn_empty_stats(index, nq, precision, backend)),
        )
    dev = index.device
    qj = jnp.asarray(queries)
    bf16 = precision == "bf16"
    eps = index.bf16_margin() if bf16 else 0.0
    eps_j = jnp.float32(eps)
    data16 = index.device_bf16 if bf16 else None
    recheck_pq = np.zeros(nq, np.int64)
    recheck_tiles_total = 0

    # The (Q, B) planar bounds are radius-independent: compute them once
    # (through the selected backend) and reuse across every round — the
    # device copy feeds the rounds, the sorted host copy drives the initial
    # radius and the per-round widening schedule.
    n_blocks = index.n_blocks
    with call.log.span("engine/knn/bounds"):
        lb_dev = _knn_lb_jit(
            metric_eng, qj, dev, bq=bq, backend=backend, interpret=interpret
        )
        lb_np = call.fetch(lb_dev)
        lb_sorted = np.sort(lb_np, axis=1)
        if r0 is None:
            j0 = min(n_blocks - 1, max(0, math.ceil(2 * k / index.block) - 1))
            radii = lb_sorted[:, j0].astype(np.float32)
        else:
            radii = np.full(nq, float(r0), np.float32)

    valid_pb = _valid_per_block(index)
    total_exact = np.zeros(nq, np.int64)
    excl_pq = np.zeros(nq, np.int64)
    tiles_total = 0
    done = np.zeros(nq, bool)
    cand_idx = np.full((nq, k_run), 0, np.int64)
    cand_dist = np.full((nq, k_run), np.inf, np.float32)
    rounds = 0
    for rounds in range(1, max_rounds + 2):
        with call.log.span("engine/knn/round", round=rounds):
            if rounds == max_rounds + 1:
                # exhaustive fallback for stragglers: radius inf computes
                # every block, so the round below is guaranteed final for
                # them.
                radii = np.where(done, radii, np.inf).astype(np.float32)
            alive_host = lb_np <= radii[:, None]  # identical to the device test
            if (backend == "jnp" and realisation != "dense"
                    and alive_host.mean() <= _DENSE_ALIVE_FRAC):
                # sparse round: gather only the alive cells (adaptive, like
                # the range path; the branch condition reads only the fp32
                # bound phase, so both precisions take it identically);
                # done/alive/tiles derived on host
                qidx, bidx = np.nonzero(alive_host)
                c = len(qidx)
                c_pad = _next_pow2(c)
                qidx_p = np.pad(qidx, (0, c_pad - c)).astype(np.int32)
                bidx_p = np.pad(bidx, (0, c_pad - c)).astype(np.int32)
                if bf16:
                    # bf16 scan picks the band cells; the UNCHANGED fp32
                    # round below then runs over just those cells — its
                    # values, tie order and outputs are exactly the fp32
                    # round's.
                    outs = _knn_round_cells_bf16_jit(
                        metric_eng, qj, data16, dev.valid,
                        jnp.asarray(qidx_p), jnp.asarray(bidx_p),
                        jnp.asarray(np.arange(c_pad) < c), eps_j,
                        k=k_run, block=index.block,
                    )
                    band_cell, band_counts = call.fetch(*outs)
                    sel = np.nonzero(band_cell)[0]
                    recheck_pq += np.where(~done, band_counts, 0)
                    qidx_p, bidx_p = qidx_p[sel], bidx_p[sel]
                    c = len(sel)
                    c_pad = _next_pow2(c)
                    qidx_p = np.pad(qidx_p, (0, c_pad - c)).astype(np.int32)
                    bidx_p = np.pad(bidx_p, (0, c_pad - c)).astype(np.int32)
                outs = _knn_round_cells_jit(
                    metric_eng, qj, dev.data, dev.valid,
                    jnp.asarray(qidx_p), jnp.asarray(bidx_p),
                    jnp.asarray(np.arange(c_pad) < c),
                    k=k_run, block=index.block,
                )
                ci, cd = call.fetch(*outs)
                kth = cd[:, -1]
                dn = np.isfinite(kth) & (
                    (kth <= radii) | alive_host.all(axis=1)
                )
                alive = alive_host
                tiles_round = int(call.fetch(
                    _tile_survival(jnp.asarray(alive_host), bq)
                ).sum())
            elif bf16:
                outs = _knn_round_bf16_jit(
                    metric_eng, qj, jnp.asarray(radii), lb_dev, dev,
                    data16, eps_j,
                    k=k_run, block=index.block, bq=bq, backend=backend,
                    interpret=interpret,
                )
                (ci, cd, kth, dn, alive, tile_mask, rtiles,
                 band_counts) = call.fetch(*outs)
                tiles_round = int(tile_mask.sum())
                recheck_tiles_total += int(rtiles)
                recheck_pq += np.where(~done, band_counts, 0)
            else:
                outs = _knn_round_jit(
                    metric_eng, qj, jnp.asarray(radii), lb_dev, dev,
                    k=k_run, block=index.block, bq=bq, backend=backend,
                    interpret=interpret,
                )
                ci, cd, kth, dn, alive, tile_mask = call.fetch(*outs)
                tiles_round = int(tile_mask.sum())
            with call.log.span("engine/knn/schedule"):
                upd = ~done  # freeze finished queries (their results are final)
                cand_idx[upd] = ci[upd]
                cand_dist[upd] = cd[upd]
                total_exact[upd] += alive[upd].astype(np.int64) @ valid_pb
                excl_pq[upd] += n_blocks - alive[upd].sum(axis=1)
                tiles_total += tiles_round
                done = done | dn
                if done.all():
                    break
                # widen to the radius that (at least) doubles the surviving
                # blocks, tighten by the kth-nearest-so-far where we already
                # hold k candidates — min() keeps the next mask as small as
                # evidence allows.
                n_alive = alive.sum(axis=1)
                j_next = np.minimum(
                    n_blocks - 1,
                    np.maximum(np.maximum(2 * n_alive, n_alive + 1), 1),
                )
                widened = np.maximum(
                    lb_sorted[np.arange(nq), j_next], radii * growth
                )
                # finished queries get a negative radius: lb >= 0, so their
                # alive rows empty out and they stop contributing
                # blocks/tiles to the remaining rounds (their results are
                # already frozen above)
                radii = np.where(
                    done, np.float32(-1.0),
                    np.where(np.isfinite(kth), np.minimum(kth, widened),
                             widened),
                ).astype(np.float32)
                # unprunable query (most blocks already alive): grinding
                # more rounds just re-evaluates them — finish exhaustively
                radii = np.where(
                    ~done & (n_alive > n_blocks // 2), np.float32(np.inf),
                    radii,
                )

    with call.log.span("engine/knn/stats"):
        n_pivots = index.pivots.shape[0]
        stats = {
            "rounds": rounds,
            "pivot_dists_per_query": float(n_pivots),
            "exact_dists_per_query": float(total_exact.mean()),
            "dists_per_query": float(n_pivots + total_exact.mean()),
            "per_query_dists": n_pivots + total_exact,
            "tiles_computed": tiles_total,
            "n_blocks": int(index.n_blocks),
            "generation": int(index.generation),
            "precision": precision,
            # rounds x blocks the Hilbert bound pruned from the exact phase,
            # accumulated per query over its unfinished rounds only
            "excluded": {"hilbert": excl_pq},
        }
        if bf16:
            _bf16_stats(stats, eps, recheck_tiles_total, recheck_pq)
        _finish_stats(stats, kind="knn", backend=backend)
        orig = np.where(np.isfinite(cand_dist), index.perm[cand_idx], -1)
        if k_run < k:  # corpus smaller than k: pad out to the requested width
            orig = np.pad(orig, ((0, 0), (0, k - k_run)), constant_values=-1)
            cand_dist = np.pad(
                cand_dist, ((0, 0), (0, k - k_run)), constant_values=np.inf
            )
    return orig, cand_dist, call.finish(stats)
