"""Pallas TPU kernel: blocked pairwise distance matrix.

This is the framework's compute hot-spot — the paper's cost model is
*distance evaluations*, and on TPU those are batched into an MXU contraction:

    d2(X, Y) = |x|^2 + |y|^2 - 2 X Y^T

Tiling: grid over (M/bm, N/bn) output tiles.  Each grid cell streams an
(bm, K) X tile and an (bn, K) Y tile from HBM into VMEM, contracts on the MXU
with fp32 accumulation, adds the squared norms (computed in-kernel on the
VPU — cheaper than two extra HBM-resident operands), and writes one output
tile.  Metric-space dims (K = 10..512) fit VMEM whole, so K is NOT tiled;
bm = bn = 128 matches the MXU systolic array and the BSS block size, making
"block pruned" == "grid cell skipped" (see masked variant).

VMEM budget per cell @ bm=bn=128, K=512, fp32:
    X tile 256 KiB + Y tile 256 KiB + out 64 KiB + norms ~1 KiB  << 16 MiB.

The masked variant consumes the BSS exclusion mask (one flag per output
tile) and skips the MXU work of excluded tiles via ``pl.when`` — the planar
lower bound of the paper materialised as *actually skipped* compute.  The
mask travels as a scalar-prefetch operand: the TPU refuses a ``(1, 1)``
VMEM block (the last two block dims must divide by (8, 128)), so the
flags are bit-packed 32 per int32 word into SMEM — 4 x 7,813 tiles (512
queries over 1M rows) is 977 words — and each grid step reads its own bit.

The l2 contraction asks for ``Precision.HIGHEST``: the TPU's default f32
matmul is one bf16 pass, whose ~1e-3 relative error would move hits across
the threshold; HIGHEST gives fp32-accurate products on the MXU (and is a
no-op on CPU).

Metric-dispatched family
------------------------
``pairwise_kernel_call`` / ``masked_pairwise_kernel_call`` dispatch one tile
kernel per supermetric: l2 (MXU contraction, this module), JSD and
Triangular (VPU broadcast reductions, ``jsd_dist`` / ``tri_dist``).  The
masked wrapper is metric-agnostic — the ``pl.when`` tile skip is applied
around whichever tile kernel the metric resolves to, so every supermetric
gets the same "block pruned == grid cell skipped" guarantee.  Cosine never
appears here: the engine serves it as l2 over unit-normalised vectors
(exact, per the supermetric cosine definition).

Mixed precision
---------------
The family is dtype-parametrised through jit: operands keep their storage
dtype across the HBM->VMEM stream and every tile kernel upcasts to fp32 ON
ENTRY (``.astype`` + ``preferred_element_type``), so accumulation is always
fp32 and the output is always an fp32 distance tile.  The bf16 exact phase
(``precision="bf16"`` in the engines) exploits exactly this: ``y`` is the
bfloat16 corpus mirror (half the streamed bytes — the dominant traffic),
``x`` stays fp32 (queries are a rounding error of the traffic, and keeping
them exact halves the comparison margin).  bf16 operands meet the TPU
minimum tile (16, 128) trivially at bn = 128; the comparison-margin
machinery that makes the halved precision EXACT lives in
``repro.core.precision`` and the engine drivers, not here — these kernels
compute the same function regardless of the storage dtype, just at the
storage dtype's rounding of ``y``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.jsd_dist import _jsd_tile_kernel
from repro.kernels.tiles import TILE_BLOCK, TILE_BQ
from repro.kernels.tri_dist import _tri_tile_kernel

__all__ = [
    "pairwise_l2_kernel_call",
    "masked_pairwise_l2_kernel_call",
    "pairwise_kernel_call",
    "masked_pairwise_kernel_call",
    "KERNEL_METRICS",
]

# overridable without a rebuild via REPRO_TILE_BQ / REPRO_TILE_BLOCK
# (see repro.kernels.tiles) — the TPU-autotuning knob.
DEFAULT_BM = TILE_BQ
DEFAULT_BN = TILE_BLOCK


def _interpret_default() -> bool:
    # Kernels TARGET TPU; everywhere else they run in interpret mode.
    return jax.default_backend() != "tpu"


def _l2_tile_kernel(x_ref, y_ref, o_ref, *, squared: bool):
    x = x_ref[...].astype(jnp.float32)  # (bm, K)
    y = y_ref[...].astype(jnp.float32)  # (bn, K)
    # MXU contraction with explicit fp32 accumulation.
    xy = jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    xx = jnp.sum(x * x, axis=1, keepdims=True)  # (bm, 1)  VPU
    yy = jnp.sum(y * y, axis=1, keepdims=True).T  # (1, bn)
    sq = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    o_ref[...] = sq if squared else jnp.sqrt(sq)


def _masked_tile_kernel(mask_ref, x_ref, y_ref, o_ref, *, tile_kernel):
    """Metric-agnostic mask wrapper: the whole compute tile is skipped when
    the BSS planar lower bound already excluded this (query-tile, block)
    cell — excluded tiles are filled with +inf without touching MXU/VPU.
    ``mask_ref`` is the bit-packed flag vector in SMEM (``_pack_mask``)."""
    flat = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    live = (mask_ref[flat // 32] >> (flat % 32)) & 1

    @pl.when(live == 0)
    def _skip():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    @pl.when(live != 0)
    def _do():
        tile_kernel(x_ref, y_ref, o_ref)


def _pack_mask(tile_mask: jnp.ndarray) -> jnp.ndarray:
    """(m_tiles, n_tiles) flags -> row-major bit vector, 32 flags per int32
    word (bit ``f % 32`` of word ``f // 32`` is flag ``f``)."""
    flat = (tile_mask.reshape(-1) != 0).astype(jnp.uint32)
    flat = _pad_to(flat, 32, 0).reshape(-1, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :]
    words = jnp.sum(flat << shifts, axis=1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


# metric name -> unmasked tile kernel (x_ref, y_ref, o_ref); the masked
# variant is derived by wrapping with _masked_tile_kernel
_TILE_KERNELS = {
    "l2": functools.partial(_l2_tile_kernel, squared=False),
    "jsd": _jsd_tile_kernel,
    "triangular": _tri_tile_kernel,
}
KERNEL_METRICS = tuple(_TILE_KERNELS)


def _pad_to(a: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


def _pairwise_call(tile_kernel, x, y, *, bm, bn, interpret):
    """Shared (grid, padding, pallas_call) plumbing for unmasked tiles."""
    m, k = x.shape
    n, k2 = y.shape
    if k != k2:
        raise ValueError(
            f"x and y must share the feature dimension: {x.shape} vs {y.shape}"
        )
    xp = _pad_to(x, bm, 0)
    yp = _pad_to(y, bn, 0)
    mp, np_ = xp.shape[0], yp.shape[0]
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]


def _masked_call(tile_kernel, x, y, tile_mask, *, bm, bn, interpret):
    """Shared plumbing for masked tiles: one mask flag per output tile,
    excluded tiles short-circuit to +inf via ``pl.when``."""
    m, k = x.shape
    n, _ = y.shape
    xp = _pad_to(x, bm, 0)
    yp = _pad_to(y, bn, 0)
    mp, np_ = xp.shape[0], yp.shape[0]
    grid = (mp // bm, np_ // bn)
    if tile_mask.shape != grid:
        raise ValueError(
            f"tile_mask shape {tile_mask.shape} does not match the "
            f"(m_tiles, n_tiles) grid {grid}"
        )
    out = pl.pallas_call(
        functools.partial(_masked_tile_kernel, tile_kernel=tile_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j, mask: (i, 0)),
                pl.BlockSpec((bn, k), lambda i, j, mask: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, mask: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(_pack_mask(tile_mask), xp, yp)
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "squared", "interpret")
)
def pairwise_l2_kernel_call(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    squared: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(m, K), (n, K) -> (m, n) Euclidean distance matrix."""
    if interpret is None:
        interpret = _interpret_default()
    return _pairwise_call(
        functools.partial(_l2_tile_kernel, squared=squared),
        x, y, bm=bm, bn=bn, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "squared", "interpret")
)
def masked_pairwise_l2_kernel_call(
    x: jnp.ndarray,
    y: jnp.ndarray,
    tile_mask: jnp.ndarray,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    squared: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Masked variant: ``tile_mask[i, j] != 0`` marks live output tiles;
    excluded tiles are filled with +inf without touching the MXU.

    ``tile_mask`` has shape (ceil(m/bm), ceil(n/bn)) — for BSS use bn = the
    index block size so mask == block-survival matrix.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _masked_call(
        functools.partial(_l2_tile_kernel, squared=squared),
        x, y, tile_mask, bm=bm, bn=bn, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("metric_name", "bm", "bn", "interpret")
)
def pairwise_kernel_call(
    metric_name: str,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Metric-dispatched (m, K), (n, K) -> (m, n) distance matrix for every
    metric in ``KERNEL_METRICS``."""
    if interpret is None:
        interpret = _interpret_default()
    return _pairwise_call(
        _TILE_KERNELS[metric_name], x, y, bm=bm, bn=bn, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("metric_name", "bm", "bn", "interpret")
)
def masked_pairwise_kernel_call(
    metric_name: str,
    x: jnp.ndarray,
    y: jnp.ndarray,
    tile_mask: jnp.ndarray,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Metric-dispatched masked pairwise: the BSS exact phase for every
    metric in ``KERNEL_METRICS``, with the same tile-skipping contract as
    ``masked_pairwise_l2_kernel_call``."""
    if interpret is None:
        interpret = _interpret_default()
    return _masked_call(
        _TILE_KERNELS[metric_name], x, y, tile_mask,
        bm=bm, bn=bn, interpret=interpret,
    )
