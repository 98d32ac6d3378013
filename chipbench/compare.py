"""The comparison that decides ``correct`` for a training cell.

Set-up drives the program's own compiled step from the seed through its
first three steps; the plain reference (``reference/<arch>.py``, float32
under ``jax.default_matmul_precision("highest")``) follows the same three
steps from the same weights and data. Three numbers are compared, each
against the limit in ``cells/<cell>.json``:

- ``loss_gap``: the largest relative gap of the three steps' losses;
- ``grad_norm_gap``: the first gradient as the optimizer got it (from
  Adam's first moment after step 1, ``m / (1 - beta1)``), by the worst
  leaf: ``| |g_prog| - |g_ref| |`` over the larger of ``|g_ref|`` and the
  median leaf's ``|g_ref|``;
- ``update_norm_gap``: the change of the parameters over the three steps,
  by the worst leaf in the same way, over the leaves whose reference
  gradient is at least a thousandth of the median leaf's (a leaf below
  that moves under Adam by round-off alone).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
MOVING = 1e-3  # share of the median leaf's gradient below which a leaf is left out


def masked_nll(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.where(mask, nll, 0).sum() / jnp.maximum(mask.sum(), 1)


def leaves(tree) -> list[np.ndarray]:
    return [np.asarray(leaf, np.float64)
            for leaf in jax.tree_util.tree_leaves(tree)]


def train_reference(ref, cfg: dict, params0, graph: dict, x, labels, mask,
                    dtype=jnp.float32) -> dict:
    """Three Adam steps of the reference, computed in ``dtype``.

    Adam as the configuration states it: ``m, v`` in float32, the bias
    correction folded into the step size, ``eps`` outside the root.
    Returns the losses, the first gradient and the parameters after
    ``STEPS`` steps, as host arrays.
    """
    opt = cfg["optimizer"]
    lr, b1, b2, eps = cfg["lr"], opt["beta1"], opt["beta2"], opt["eps"]
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    arrays = {k: v for k, v in graph.items() if isinstance(v, jax.Array)}
    static = {k: v for k, v in graph.items() if k not in arrays}

    # data rides as arguments: closed over, it would be embedded in the
    # program as constants (gigabytes at the cells' sizes)
    def loss(p, arrays, x, labels, mask):
        g = {**static, **{k: v.astype(dtype) if k == "w" else v
                          for k, v in arrays.items()}}
        out = ref.logits(cast(p), g, x.astype(dtype), cfg)
        return masked_nll(out, labels, mask).astype(jnp.float32)

    grad = jax.jit(jax.value_and_grad(loss))

    @jax.jit
    def adam(p, g, m, v, t):
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        step = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - step * m / (jnp.sqrt(v) + eps), p, m, v)
        return p, m, v

    p = params0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad1 = [], None
    for t in range(1, STEPS + 1):
        value, g = grad(p, arrays, x, labels, mask)
        g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
        losses.append(float(value))
        if grad1 is None:
            grad1 = leaves(g)
        p, m, v = adam(p, g, m, v, jnp.float32(t))
    return {"losses": losses, "grad1": grad1, "params": leaves(p)}


def _norms(arrays) -> np.ndarray:
    return np.array([np.linalg.norm(a) for a in arrays])


def leaf_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per leaf, ``|got_i - want_i| / max(want_i, median(want))``, where
    ``got``/``want`` are per-leaf norms."""
    return np.abs(got - want) / np.maximum(want, np.median(want))


def per_leaf(prog: dict, ref: dict, params0: list[np.ndarray]) -> dict:
    """Each leaf's gap of the first gradient and of the change (NaN for
    the leaves the change leaves out). ``prog``/``ref`` as
    ``train_reference`` returns them; ``params0`` the starting leaves."""
    g_ref = _norms(ref["grad1"])
    moving = g_ref >= MOVING * np.median(g_ref)
    d_prog = _norms([p - q for p, q in zip(prog["params"], params0)])
    d_ref = _norms([p - q for p, q in zip(ref["params"], params0)])
    update = np.full(len(g_ref), np.nan)
    update[moving] = leaf_gaps(d_prog[moving], d_ref[moving])
    return {"grad": leaf_gaps(_norms(prog["grad1"]), g_ref),
            "update": update}


def readings(prog: dict, ref: dict, params0: list[np.ndarray]) -> dict:
    """The three compared numbers."""
    lp, lr_ = np.array(prog["losses"]), np.array(ref["losses"])
    gaps = per_leaf(prog, ref, params0)
    return {"loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
            "grad_norm_gap": float(np.max(gaps["grad"])),
            "update_norm_gap": float(np.nanmax(gaps["update"]))}


def judge(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}``; a value passes when it is at most its
    limit (a NaN never passes)."""
    return {k: {"value": float(values[k]), "limit": float(limits[k])}
            for k in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
