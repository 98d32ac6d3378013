"""Plain GAT over the edge list, DGL's ogbn-arxiv ``GATConv`` stack.

Per layer, with ``K`` heads of width ``D`` and ``j`` over the sources of
``i``'s incoming edges (self loops included)::

    Z^k = H·W^k,  s_i^k = a_dst^k·Z_i^k,  t_j^k = a_src^k·Z_j^k
    α_ij^k = softmax over j of leaky_relu_0.2(s_i^k + t_j^k)
    O_i^k = Σ_j α_ij^k Z_j^k + (H·W_res)_i^k
    hidden:  H' = relu(concat_k O^k + b)        last:  (1/K) Σ_k O^k + b

Straight ``jax.numpy``; the caller sets the precision. Each head's
aggregation runs under ``jax.checkpoint``, so that one ``[E, D]`` message
tensor is live at a time, and its backward recomputes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SLOPE = 0.2


def xavier(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(
        2.0 / (shape[0] + shape[-1]))


def init(key, dims, cfg) -> dict:
    heads = cfg["heads"]
    layers = []
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        last = i == len(dims) - 2
        d = d_out if last else d_out // heads
        k = jax.random.split(jax.random.fold_in(key, i), 4)
        layers.append({"w": xavier(k[0], (d_in, heads * d)),
                       "a_src": xavier(k[1], (heads, d)),
                       "a_dst": xavier(k[2], (heads, d)),
                       "w_res": xavier(k[3], (d_in, heads * d)),
                       "b": jnp.zeros((d if last else heads * d,),
                                      jnp.float32)})
    return {"layers": layers}


def prepare(src, dst, n: int) -> dict:
    return {"src": src, "dst": dst, "n": n}


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _head(z, a_src, a_dst, src, dst, n):
    """One head: ``Σ_j α_ij z_j`` for every ``i``, ``z [n, D]``."""
    pre = (z @ a_dst)[dst] + (z @ a_src)[src]
    e = jnp.where(pre >= 0, pre, SLOPE * pre)
    m = jax.ops.segment_max(e, dst, num_segments=n)
    m = jnp.where(jnp.isfinite(m), m, 0)
    p = jnp.exp(e - m[dst])
    l = jax.ops.segment_sum(p, dst, num_segments=n)
    out = jax.ops.segment_sum(p[:, None] * z[src], dst, num_segments=n)
    return out / jnp.maximum(l, 1e-20)[:, None]


def logits(params, graph, x, cfg):
    src, dst, n = graph["src"], graph["dst"], graph["n"]
    heads = cfg["heads"]
    h = x
    layers = params["layers"]
    for i, layer in enumerate(layers):
        z = (h @ layer["w"]).reshape(n, heads, -1)
        res = (h @ layer["w_res"]).reshape(n, heads, -1)
        o = jnp.stack([_head(z[:, k], layer["a_src"][k], layer["a_dst"][k],
                             src, dst, n) for k in range(heads)], axis=1)
        o = o + res
        if i == len(layers) - 1:
            h = o.mean(axis=1) + layer["b"]
        else:
            h = jax.nn.relu(o.reshape(n, -1) + layer["b"])
    return h
