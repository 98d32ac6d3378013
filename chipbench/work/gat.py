"""Useful work of one full-batch GAT epoch: forward, loss, backward, Adam.

Counted from the graph and the widths alone (nodes ``n``, nonzeros of the
adjacency with self loops ``nnz``, nonzeros of the features ``x_nnz``,
layer widths ``dims``, heads ``heads``), never from blocks, tiles, packed
lanes or padding, so any implementation of the same layer is read against
the same work. Float32, 4 bytes a word.

Per layer with input width ``d_in``, ``K`` heads of width ``D`` (``W =
K·D``: ``dims[l+1]`` in hidden layers, ``K·dims[l+1]`` in the last, whose
heads are averaged):

- transforms ``Z = H·W`` and ``R = H·W_res``: ``2·n·d_in·W`` each (layer 0:
  ``2·x_nnz·W`` each); backward ``dW``, ``dW_res``: the same again; ``dH``
  through both for every layer but the first: ``2 · 2·n·d_in·W``;
- scores ``s = a_dst·Z``, ``t = a_src·Z``: ``2·n·W`` each; backward, their
  gradients into ``Z`` and into ``a_src``, ``a_dst``: ``4 · 2·n·W``;
- attention forward over A (``fwd``): ``2·nnz·W`` for ``Σ_j p_ij Z_j`` and
  ``5·nnz·K`` for the scores and the softmax (add, LeakyReLU, max,
  subtract and exponent; the denominator's sum and the rescaling are
  counted with them);
- backward row pass over A (``bwd_row``): ``2·nnz·W`` for ``dY_i·Z_j`` and
  ``8·nnz·K`` to recompute the weights and form ``dpre``;
- backward column pass over Aᵀ (``bwd_col``): ``2·nnz·W`` for
  ``Σ_i α_ij dY_i``, ``2·nnz·W`` for ``dY_i·Z_j`` and ``8·nnz·K``.

Least bytes of each attention kernel: the sparse operand once (``nnz``
column indices, ``n + 1`` row pointers), each per-node input read once and
each output written once:

- ``fwd``: ``Z`` and ``t`` of the sources, ``s`` of the destinations;
  writes ``out`` and the row statistics ``m``, ``l``;
- ``bwd_row``: ``Z``, ``t``, ``dY``, ``s``, ``m``, ``l``, ``r``; writes ``dc``;
- ``bwd_col`` (over Aᵀ): ``dY``, ``s``, ``m``, ``l``, ``r`` of the
  destinations, ``Z``, ``t`` of the sources; writes ``dZ_v`` and ``dd``.

Element-wise work (bias, ReLU, the residual add, the head mean, softmax of
the logits, Adam) is left out: under one percent of the total here.
"""
from __future__ import annotations

F32 = IDX = 4  # bytes
KERNELS = ("fwd", "bwd_row", "bwd_col")


def layer_widths(dims: list[int], heads: int):
    """``(d_in, W, K)`` of each layer."""
    last = len(dims) - 2
    return [(d_in, heads * d_out if i == last else d_out, heads)
            for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:]))]


def attention_work(n: int, nnz: int, w: int, k: int) -> dict:
    """``{kernel: (flops, least bytes)}`` of one layer's three attention
    kernels."""
    csr = nnz * IDX + (n + 1) * IDX
    return {
        "fwd": (2 * nnz * w + 5 * nnz * k,
                csr + n * (w + 2 * k) * F32 + n * (w + 2 * k) * F32),
        "bwd_row": (2 * nnz * w + 8 * nnz * k,
                    csr + n * (2 * w + 5 * k) * F32 + n * k * F32),
        "bwd_col": (4 * nnz * w + 8 * nnz * k,
                    csr + n * (2 * w + 5 * k) * F32 + n * (w + k) * F32),
    }


def epoch_work(stats: dict) -> dict:
    n, nnz, x_nnz = stats["n"], stats["nnz"], stats["x_nnz"]
    flops = 0
    attn = {name: [0, 0] for name in KERNELS}
    for i, (d_in, w, k) in enumerate(layer_widths(stats["dims"],
                                                  stats["heads"])):
        rows = x_nnz if i == 0 else n * d_in  # multiply-adds per output lane
        flops += 2 * (2 * rows * w)  # Z, R forward
        flops += 2 * (2 * rows * w)  # dW, dW_res
        if i:
            flops += 2 * (2 * n * d_in * w)  # dH through W and W_res
        flops += 2 * (2 * n * w) + 4 * (2 * n * w)  # scores and their grads
        for name, (f, b) in attention_work(n, nnz, w, k).items():
            attn[name][0] += f
            attn[name][1] += b
    attn_flops = sum(f for f, _ in attn.values())
    attn_bytes = sum(b for _, b in attn.values())
    return {"flops": flops + attn_flops, "sparse_flops": attn_flops,
            "sparse_bytes": attn_bytes,
            "attention": {name: tuple(v) for name, v in attn.items()}}
