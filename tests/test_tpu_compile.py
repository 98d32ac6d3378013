"""Mosaic compiles every Pallas kernel of the main path for a described TPU
v5e, at ogbn-arxiv's published size (169,343 nodes; 917,090 blocks at the
(8, 128) tile, 1,081,262 at (8, 16); 1,320,039 nonzeros for the CSR
row-gather and window kernels).

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
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AbstractDevice, AbstractMesh, SingleDeviceSharding

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
    WINDOW_TILE,
    csr_gather_spmm,
    csr_gather_spmm_fused_epilogue,
    csr_gather_spmm_masked,
    window_rows,
)
from repro.kernels import ops as kops
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


@pytest.fixture(scope="module")
def vmem(topo):
    """The VMEM bytes ``pltpu.get_tpu_info`` reports for the described
    chip: the capacity the window kernels size their windows by."""
    d = topo.devices[0]
    chip = AbstractMesh((1,), ("x",), abstract_device=AbstractDevice(
        device_kind=d.device_kind, num_cores=d.num_cores))
    with jax.sharding.use_abstract_mesh(chip):
        return pltpu.get_tpu_info().vmem_capacity_bytes


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


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


# each SpMM entry point, its keyword arguments and its arguments after the
# CSR, by the entry point's name
GATHER = {
    "csr_gather_spmm": lambda shape, n, f: (
        csr_gather_spmm, {}, shape((n, f))),
    "csr_gather_spmm_fused_epilogue": lambda shape, n, f: (
        csr_gather_spmm_fused_epilogue, {"activation": "relu"},
        shape((n, f)), shape((n, f)), shape((f,)), shape(())),
    "csr_gather_spmm_masked": lambda shape, n, f: (
        csr_gather_spmm_masked, {}, shape((n, f)), shape((n, f))),
}


# the window path of each entry point, by its kernel's name
WINDOW = {
    "csr_window_spmm": lambda shape, n, f, w: (
        lambda *a: csr_gather_spmm(*a, n_rows=n, window=w,
                                   tm=WINDOW_TILE), shape((n, f))),
    "csr_window_spmm_fused_epilogue": lambda shape, n, f, w: (
        lambda *a: csr_gather_spmm_fused_epilogue(
            *a, n_rows=n, window=w, tm=WINDOW_TILE, activation="relu"),
        shape((n, f)), shape((n, f)), shape((f,)), shape(())),
    "csr_window_spmm_masked": lambda shape, n, f, w: (
        lambda *a: csr_gather_spmm_masked(*a, n_rows=n, window=w,
                                          tm=WINDOW_TILE),
        shape((n, f)), shape((n, f))),
}


def _window_calls(text):
    """The Mosaic custom calls of compiled HLO, one line each."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("f", [256, 40])
@pytest.mark.parametrize("name", list(GATHER))
def test_csr_gather_compiles(shape, vmem, monkeypatch, name, f):
    """Each SpMM entry point at arxiv's size as the program dispatches it
    (``kops.gather_pass`` over a tile-column operand), with the windows
    the described chip's VMEM holds: two at 256 lanes, one at 40. SMEM
    holds one window of tile bounds and two chunks of indices whatever
    nnz is."""
    monkeypatch.setattr(kops, "chip_vmem", lambda: vmem)
    entry, kw, x, *rest = GATHER[name](shape, N_NODES, f)

    def fn(*a):
        return kops.gather_pass(entry, a[:4], WINDOW_TILE, a[4], *a[5:],
                                n_rows=N_NODES, interpret=False, **kw)

    text = _compile(fn, *_csr(shape), x, *rest)
    assert len(_window_calls(text)) == (2 if f == 256 else 1)


@pytest.mark.parametrize("f,n_windows", [(256, 2), (128, 1)])
@pytest.mark.parametrize("name", list(WINDOW))
def test_csr_window_compiles(shape, vmem, name, f, n_windows):
    """The window kernels at arxiv's size, with the window the chip's VMEM
    holds beside the tiles (``window_rows``) and the ``vmem_limit_bytes``
    that sets: one call per window, each holding its window of ``x`` in
    VMEM."""
    fn, *rest = WINDOW[name](shape, N_NODES, f,
                             window_rows(f, WINDOW_TILE, vmem))
    assert len(_window_calls(_compile(fn, *_csr(shape), *rest))) == n_windows


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
    name: lambda shape, stream, n, _name=name: (
        lambda fn, *rest: (fn, *_csr(shape, n, 4096), *rest))(
            *WINDOW[_name](shape, n, 128, n // 2))
    for name in WINDOW})
KERNELS.update({
    name: lambda shape, stream, n, _name=name: _gather_attention(
        shape, _name, n, 4096, 120)
    for name in GATHER_ATTENTION})


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_lowers_under_its_own_name(shape, name):
    """Every Mosaic custom call of a kernel carries the kernel's
    ``kernel_name`` (BSR: one call per window, 64 blocks in windows of 16;
    CSR SpMM: one call per source window of 512 rows; CSR attention: one
    call), so traces and
    compiled HLO find it by that name; none is ``kernel``."""
    n = 1024
    stream = _stream(shape, 64, 8, 128)
    fn, *args = KERNELS[name](shape, stream, n)
    text = jax.jit(fn).lower(*args).as_text()
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert names and set(names) == {name}, names
