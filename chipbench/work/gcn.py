"""Useful work of one full-batch GCN epoch: forward, loss, backward, Adam.

Counted from the graph and the widths alone (nodes ``n``, nonzeros of the
adjacency with self loops ``nnz``, nonzeros of the features ``x_nnz``,
layer widths ``dims``), never from blocks, tiles or padding, so any
implementation of the same layer is read against the same work.

Per layer ``l`` with widths ``d_in -> d_out`` (``relu(Â·(H·W) + b)``):

- transform ``H·W``: ``2·n·d_in·d_out`` (layer 0: ``2·x_nnz·d_out``);
- aggregation ``Â·U`` forward and ``Âᵀ·dY`` backward: ``2·nnz·d_out``
  each, and the least bytes each needs: the adjacency once (``nnz``
  column indices and values, ``n + 1`` row pointers), its input read once
  and its output written once, in float32;
- ``dW = Hᵀ·dU``: ``2·n·d_in·d_out`` (layer 0: ``2·x_nnz·d_out``);
- ``dH = dU·Wᵀ`` for every layer but the first: ``2·n·d_in·d_out``.

Element-wise work (bias, ReLU, softmax, Adam) is left out: it is under
one percent of the total here. Where the features are mostly zeros
(``x_nnz <= n·f / 2``), the layer-0 products ``X·W`` and ``Xᵀ·dU`` are
sparse-operand products too, with ``X`` read once as ``x_nnz`` indices and
values.
"""
from __future__ import annotations

F32 = IDX = 4  # bytes


def spmm_bytes(n_rows: int, n_cols: int, nnz: int, width: int) -> int:
    """Least bytes of ``Y[n_rows, width] = S[n_rows, n_cols] @ X``."""
    return (nnz * (IDX + F32) + (n_rows + 1) * IDX
            + (n_cols + n_rows) * width * F32)


def x_is_sparse(n: int, f: int, x_nnz: int) -> bool:
    return 2 * x_nnz <= n * f


def feature_products(n: int, f: int, x_nnz: int, d_out: int):
    """(flops, sparse flops, sparse bytes) of layer 0's ``X·W`` and
    ``dW = Xᵀ·dU``."""
    flops = 2 * (2 * x_nnz * d_out)
    if not x_is_sparse(n, f, x_nnz):
        return flops, 0, 0
    return flops, flops, (spmm_bytes(n, f, x_nnz, d_out)
                          + spmm_bytes(f, n, x_nnz, d_out))


def epoch_work(stats: dict) -> dict:
    n, nnz, x_nnz, dims = stats["n"], stats["nnz"], stats["x_nnz"], stats["dims"]
    flops, sparse_flops, sparse_bytes = feature_products(n, dims[0], x_nnz,
                                                         dims[1])
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        if i:
            flops += 3 * 2 * n * d_in * d_out  # H·W, dW, dH
        agg = 2 * (2 * nnz * d_out)
        flops += agg
        sparse_flops += agg
        sparse_bytes += 2 * spmm_bytes(n, n, nnz, d_out)
    return {"flops": flops, "sparse_flops": sparse_flops,
            "sparse_bytes": sparse_bytes}
