"""Compile every Pallas kernel entry point of the served path for a
described TPU v5e chip, at the smoke's deployment widths.

Nothing runs: the TPU compiler (libtpu) compiles for a chip that is
described, not attached, and refuses what the chip would refuse — blocks
that break the (8, 128) rule, 3-D gathers, scoped-VMEM overflows — which
interpret mode on the CPU cannot show.  ``interpret=False`` is passed
explicitly (off-TPU the kernels default to interpret mode).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, so the test workers must all
collect the same tests and only the one given this file loads it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import jsd_dist, pairwise_dist, planar_exclusion

Q = 512            # the serving front's largest bucket
N_ROWS = 1_000_064  # 1M rows padded to whole 128-row blocks
N_BLOCKS = N_ROWS // 128
N_PAIRS = 24       # build_bss default planes
DIMS = {"l2": 128, "jsd": 64, "triangular": 64}  # sift-128 / topics-64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu here
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("metric", sorted(DIMS))
def test_pairwise_kernel_compiles(one_chip, metric):
    d = DIMS[metric]
    _assert_kernel(
        lambda x, y: pairwise_dist.pairwise_kernel_call(
            metric, x, y, interpret=False
        ),
        _sds(one_chip, (Q, d)), _sds(one_chip, (N_ROWS, d)),
    )


def test_pairwise_l2_kernel_compiles(one_chip):
    _assert_kernel(
        lambda x, y: pairwise_dist.pairwise_l2_kernel_call(
            x, y, interpret=False
        ),
        _sds(one_chip, (Q, 128)), _sds(one_chip, (N_ROWS, 128)),
    )


def test_standalone_jsd_kernel_compiles(one_chip):
    _assert_kernel(
        lambda x, y: jsd_dist.pairwise_jsd_kernel_call(x, y, interpret=False),
        _sds(one_chip, (Q, 64)), _sds(one_chip, (N_ROWS, 64)),
    )


@pytest.mark.parametrize("corpus_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("metric", sorted(DIMS))
def test_masked_kernel_compiles(one_chip, metric, corpus_dtype):
    """The BSS exact phase: the tile mask rides scalar prefetch (SMEM);
    the bf16 leg is the engines' half-width corpus mirror."""
    d = DIMS[metric]
    _assert_kernel(
        lambda x, y, m: pairwise_dist.masked_pairwise_kernel_call(
            metric, x, y, m, interpret=False
        ),
        _sds(one_chip, (Q, d)),
        _sds(one_chip, (N_ROWS, d), jnp.dtype(corpus_dtype)),
        _sds(one_chip, (Q // 128, N_BLOCKS), jnp.bool_),
    )


def test_masked_l2_kernel_compiles_at_forest_width(one_chip):
    """The forest walker's leaf scan: colors rows are 112-d."""
    rows = 112_768  # 112,682 colors rows padded to 128
    _assert_kernel(
        lambda x, y, m: pairwise_dist.masked_pairwise_l2_kernel_call(
            x, y, m, interpret=False
        ),
        _sds(one_chip, (Q, 112)), _sds(one_chip, (rows, 112)),
        _sds(one_chip, (Q // 128, rows // 128), jnp.bool_),
    )


def test_planar_lower_bound_kernel_compiles(one_chip):
    _assert_kernel(
        lambda d1, d2, dl, bx: planar_exclusion.planar_lower_bound_kernel_call(
            d1, d2, dl, bx, interpret=False
        ),
        _sds(one_chip, (Q, N_PAIRS)), _sds(one_chip, (Q, N_PAIRS)),
        _sds(one_chip, (N_PAIRS,)), _sds(one_chip, (4, N_PAIRS, N_BLOCKS)),
    )
