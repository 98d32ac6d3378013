"""Mosaic compiles every Pallas kernel of the main path for a described TPU
v5e, at ogbn-arxiv's published size (169,343 nodes; 917,090 blocks at the
(8, 128) tile, 1,081,262 at (8, 16); 1,320,039 nonzeros for the CSR
row-gather kernels).

Nothing runs: each test lowers and compiles against the topology only, so
it catches what interpret mode cannot — SMEM overflow from scalar-prefetched
index streams, and block shapes the chip's tiling rules refuse. The
topology is described inside a fixture (never at import), and the tests
skip where no TPU compiler is installed.
"""
import os
import re

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
from repro.kernels.csr_gather_attention import (
    csr_gather_attention_bwd_col,
    csr_gather_attention_bwd_row,
    csr_gather_attention_fwd,
)
from repro.kernels.csr_gather_spmm import (
    csr_gather_spmm,
    csr_gather_spmm_fused_epilogue,
    csr_gather_spmm_masked,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_adam import fused_adam
from repro.kernels.ops import feature_tile

N_PAD = 169_344  # ogbn-arxiv's 169,343 nodes padded to the tile
N_NODES, NNZ = 169_343, 1_320_039  # ogbn-arxiv with self loops
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


def _csr(shape, n=N_NODES, nnz=NNZ):
    """(indptr, indices, rows, values) of an n-row CSR."""
    idx = shape((nnz,), jnp.int32)
    return shape((n + 1,), jnp.int32), idx, idx, shape((nnz,))


GATHER = {
    "csr_gather_spmm": lambda shape, n, f: (
        lambda *a: csr_gather_spmm(*a, n_rows=n), shape((n, f))),
    "csr_gather_spmm_fused_epilogue": lambda shape, n, f: (
        lambda *a: csr_gather_spmm_fused_epilogue(
            *a, n_rows=n, activation="relu"),
        shape((n, f)), shape((n, f)), shape((f,)), shape(())),
    "csr_gather_spmm_masked": lambda shape, n, f: (
        lambda *a: csr_gather_spmm_masked(*a, n_rows=n),
        shape((n, f)), shape((n, f))),
}


def _gather(shape, name, n, nnz, f):
    """``(fn, *args)`` of one row-gather kernel over a CSR of ``n`` rows."""
    fn, *rest = GATHER[name](shape, n, f)
    return (fn, *_csr(shape, n, nnz), *rest)


@pytest.mark.parametrize("f", [256, 40])
@pytest.mark.parametrize("name", list(GATHER))
def test_csr_gather_compiles(shape, name, f):
    """The row-gather kernels at arxiv's size: SMEM holds one window of
    row pointers and two chunks of indices whatever nnz is, and the gather
    buffer two chunks of full-width rows in VMEM."""
    _compile(*_gather(shape, name, N_NODES, NNZ, f))


GATHER_ATTENTION = {
    "csr_gather_attention_fwd": lambda shape, n, w, k: (
        lambda *a: csr_gather_attention_fwd(*a[:3], *a[4:], heads=k,
                                            n_rows=n),
        shape((n, w)), shape((n, k)), shape((n, k))),
    "csr_gather_attention_bwd_row": lambda shape, n, w, k: (
        lambda *a: csr_gather_attention_bwd_row(*a[:3], *a[4:], heads=k,
                                                n_rows=n),
        shape((n, w)), shape((n, k)), shape((n, k)), shape((n, w)),
        *[shape((n, k))] * 3),
    "csr_gather_attention_bwd_col": lambda shape, n, w, k: (
        lambda *a: csr_gather_attention_bwd_col(*a[:3], *a[4:], heads=k,
                                                n_rows=n),
        shape((n, k)), shape((n, k)), shape((n, w)), shape((n, w)),
        *[shape((n, k))] * 3),
}


def _gather_attention(shape, name, n, nnz, w, heads=3):
    """``(fn, *args)`` of one gather-attention kernel over a CSR of ``n``
    rows (its values ride along unused)."""
    fn, *rest = GATHER_ATTENTION[name](shape, n, w, heads)
    return (fn, *_csr(shape, n, nnz), *rest)


@pytest.mark.parametrize("w", [750, 120])
@pytest.mark.parametrize("name", list(GATHER_ATTENTION))
def test_csr_gather_attention_compiles(shape, name, w):
    """The gather-attention kernels at arxiv's size and the published GAT's
    widths (3 heads of 250, concatenated; 3 heads of 40 in the last
    layer): two chunks of packed rows in VMEM, up to 768 lanes each."""
    _compile(*_gather_attention(shape, name, N_NODES, NNZ, w))


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


def _attention_fwd(shape, stream, n, heads=8, dh=8):
    stat = shape((n, heads))
    rows, cols, first, blocks = stream
    return (lambda *a: bsr_attention_fwd(*a, n_rows_padded=n, heads=heads,
                                         dh=dh, window=16),
            rows, cols, first, first, blocks, stat, stat,
            shape((n, heads * dh)))


def _attention_bwd(kernel):
    def build(shape, stream, n, heads=8, dh=8):
        stat, feat = shape((n, heads)), shape((n, heads * dh))
        return (lambda *a: kernel(*a, n_rows_padded=n, heads=heads, dh=dh,
                                  window=16),
                *stream, stat, stat, feat, feat, stat, stat, stat)
    return build


KERNELS = {
    "bsr_spmm": lambda shape, stream, n: (
        lambda *a: bsr_spmm(*a, n_rows_padded=n, window=16),
        *stream, shape((n, 128))),
    "bsr_spmm_fused_epilogue": lambda shape, stream, n: (
        lambda *a: bsr_spmm_fused_epilogue(
            *a, n_rows_padded=n, activation="relu", window=16),
        *stream[:3], stream[2], stream[3], shape((n, 128)),
        shape((n, 128)), shape((1, 128)), shape(())),
    "bsr_spmm_masked": lambda shape, stream, n: (
        lambda *a: bsr_spmm_masked(*a, n_rows_padded=n, window=16),
        *stream, shape((n, 128)), shape((n, 128))),
    "bsr_attention_fwd": _attention_fwd,
    "bsr_attention_bwd_row": _attention_bwd(bsr_attention_bwd_row),
    "bsr_attention_bwd_col": _attention_bwd(bsr_attention_bwd_col),
    "fused_adam": lambda shape, stream, n: (
        lambda *a: fused_adam(*a), *[shape((256, 256))] * 4, shape(())),
    "flash_attention": lambda shape, stream, n: (
        lambda *a: flash_attention(*a), *[shape((1, 2, 256, 128))] * 3),
}


KERNELS.update({
    name: lambda shape, stream, n, _name=name: _gather(shape, _name, n, 4096,
                                                       128)
    for name in GATHER})
KERNELS.update({
    name: lambda shape, stream, n, _name=name: _gather_attention(
        shape, _name, n, 4096, 120)
    for name in GATHER_ATTENTION})


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_lowers_under_its_own_name(shape, name):
    """Every Mosaic custom call of a kernel carries the kernel's
    ``kernel_name`` (BSR: one call per window, 64 blocks in windows of 16;
    CSR: one call), so traces and compiled HLO find it by that name; none
    is ``kernel``."""
    n = 1024
    stream = _stream(shape, 64, 8, 128)
    fn, *args = KERNELS[name](shape, stream, n)
    text = jax.jit(fn).lower(*args).as_text()
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert names and set(names) == {name}, names
