"""Plain GCN over the edge list: ``H' = relu(Â·(H·W) + b)``, no ReLU on the
last layer, ``Â = D_in^-1/2 · A · D_out^-1/2`` over the generated edges
(self loops included), with ``D_in`` / ``D_out`` the row and column
nonzero counts. Straight ``jax.numpy``; the caller sets the precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def xavier(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(
        2.0 / (shape[0] + shape[-1]))


def init(key, dims, cfg) -> dict:
    keys = jax.random.split(key, len(dims) - 1)
    return {"layers": [{"w": xavier(k, (d_in, d_out)),
                        "b": jnp.zeros((d_out,), jnp.float32)}
                       for k, d_in, d_out in zip(keys, dims[:-1], dims[1:])]}


def prepare(src, dst, n: int) -> dict:
    ones = jnp.ones(src.shape, jnp.float32)
    deg_in = jax.ops.segment_sum(ones, dst, num_segments=n)
    deg_out = jax.ops.segment_sum(ones, src, num_segments=n)
    w = jax.lax.rsqrt(jnp.maximum(deg_in, 1.0))[dst] * jax.lax.rsqrt(
        jnp.maximum(deg_out, 1.0))[src]
    return {"src": src, "dst": dst, "w": w, "n": n}


def logits(params, graph, x, cfg):
    src, dst, w, n = graph["src"], graph["dst"], graph["w"], graph["n"]
    h = x
    layers = params["layers"]
    for i, layer in enumerate(layers):
        u = h @ layer["w"]
        y = jax.ops.segment_sum(w.astype(u.dtype)[:, None] * u[src], dst,
                                num_segments=n) + layer["b"]
        h = y if i == len(layers) - 1 else jax.nn.relu(y)
    return h
