"""Mosaic compiles every Pallas kernel of the main path for a described TPU
v5e, at ogbn-arxiv's published size (169,343 nodes; 917,090 blocks at the
(8, 128) tile, 1,081,262 at (8, 16)).

Nothing runs: each test lowers and compiles against the topology only, so
it catches what interpret mode cannot — SMEM overflow from scalar-prefetched
index streams, and block shapes the chip's tiling rules refuse. The
topology is described inside a fixture (never at import), and the tests
skip where no TPU compiler is installed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_attention import (
    bsr_attention_bwd_col,
    bsr_attention_bwd_row,
    bsr_attention_fwd,
)
from repro.kernels.bsr_spmm import (
    bsr_spmm,
    bsr_spmm_fused_epilogue,
    bsr_spmm_masked,
)
from repro.kernels.fused_adam import fused_adam
from repro.kernels.ops import feature_tile

N_PAD = 169_344  # ogbn-arxiv's 169,343 nodes padded to the tile
TILES = [pytest.param(8, 16, 1_081_262, id="8x16"),
         pytest.param(8, 128, 917_090, id="8x128")]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> an abstract operand on one described chip,
    with the persistent compile cache off (its entries for a described
    chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stream(shape, n_blocks, br, bc):
    idx = shape((n_blocks,), jnp.int32)
    return idx, idx, idx, shape((n_blocks, br, bc))


@pytest.mark.parametrize("f", [256, 40])
@pytest.mark.parametrize("br,bc,n_blocks", TILES)
def test_bsr_spmm_compiles(shape, br, bc, n_blocks, f):
    rows, cols, first, blocks = _stream(shape, n_blocks, br, bc)
    bf, _ = feature_tile(f)
    _compile(lambda *a: bsr_spmm(*a, n_rows_padded=N_PAD, bf=bf),
             rows, cols, first, blocks, shape((N_PAD, f)))


@pytest.mark.parametrize("f", [256, 40])
@pytest.mark.parametrize("br,bc,n_blocks", TILES)
def test_bsr_spmm_fused_epilogue_compiles(shape, br, bc, n_blocks, f):
    rows, cols, first, blocks = _stream(shape, n_blocks, br, bc)
    bf, _ = feature_tile(f)
    _compile(lambda *a: bsr_spmm_fused_epilogue(
        *a, n_rows_padded=N_PAD, bf=bf, activation="relu"),
        rows, cols, first, first, blocks, shape((N_PAD, f)),
        shape((N_PAD, f)), shape((1, f)), shape(()))


@pytest.mark.parametrize("f", [256, 40])
@pytest.mark.parametrize("br,bc,n_blocks", TILES)
def test_bsr_spmm_masked_compiles(shape, br, bc, n_blocks, f):
    rows, cols, first, blocks = _stream(shape, n_blocks, br, bc)
    bf, _ = feature_tile(f)
    _compile(lambda *a: bsr_spmm_masked(*a, n_rows_padded=N_PAD, bf=bf),
             rows, cols, first, blocks, shape((N_PAD, f)),
             shape((N_PAD, f)))


HEADS = [pytest.param(4, 64, id="4x64"), pytest.param(8, 8, id="8x8")]


@pytest.mark.parametrize("heads,dh", HEADS)
@pytest.mark.parametrize("br,bc,n_blocks", TILES)
def test_bsr_attention_fwd_compiles(shape, br, bc, n_blocks, heads, dh):
    rows, cols, first, blocks = _stream(shape, n_blocks, br, bc)
    stat = shape((N_PAD, heads))
    _compile(lambda *a: bsr_attention_fwd(
        *a, n_rows_padded=N_PAD, heads=heads, dh=dh),
        rows, cols, first, first, blocks, stat, stat,
        shape((N_PAD, heads * dh)))


@pytest.mark.parametrize("heads,dh", HEADS)
@pytest.mark.parametrize("br,bc,n_blocks", TILES)
@pytest.mark.parametrize("kernel", [bsr_attention_bwd_row,
                                    bsr_attention_bwd_col],
                         ids=["row", "col"])
def test_bsr_attention_bwd_compiles(shape, kernel, br, bc, n_blocks, heads,
                                    dh):
    rows, cols, first, blocks = _stream(shape, n_blocks, br, bc)
    stat, feat = shape((N_PAD, heads)), shape((N_PAD, heads * dh))
    _compile(lambda *a: kernel(*a, n_rows_padded=N_PAD, heads=heads, dh=dh),
             rows, cols, first, blocks, stat, stat, feat, feat, stat, stat,
             stat)


def test_fused_adam_compiles(shape):
    p = shape((256, 256))
    _compile(lambda *a: fused_adam(*a), p, p, p, p, shape(()))
