"""Flash (tiled, online-softmax) causal attention — Pallas TPU kernel.

The LM substrate's perf-critical hot-spot: the §Roofline analysis shows
attention's O(S²) score materialisation driving the memory term for every
attention arch at train/prefill shapes. This kernel never writes the
(Tq, Tk) score matrix to HBM: the grid walks (batch·head, q-block, k-block)
with the canonical running-max/denominator recurrence held in VMEM scratch,
and the output tile is rescaled in place as blocks stream through.

Grid layout (sequential on TPU, so the k-dim accumulation is race-free by
construction, same property the BSR kernel uses):

    grid = (B·H, Tq/bq, Tk/bk)       # k innermost: out tile revisited
    scratch: m [bq], l [bq], acc [bq, D]   (f32, VMEM)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq, bk, scale, causal, t_k_valid, n_kblocks):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = cols < t_k_valid  # mask K padding
    if causal:
        valid = valid & (cols <= rows)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = (
        acc_ref[...] * alpha[:, None]
        + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kblocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, H, Tk, D]
    v: jax.Array,  # [B, H, Tk, D]
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    bq = min(bq, max(tq, 8))
    bk = min(bk, max(tk, 8))

    tq_pad = -(-tq // bq) * bq
    tk_pad = -(-tk // bk) * bk
    qf = jnp.pad(q.reshape(b * h, tq, d), ((0, 0), (0, tq_pad - tq), (0, 0)))
    kf = jnp.pad(k.reshape(b * h, tk, d), ((0, 0), (0, tk_pad - tk), (0, 0)))
    vf = jnp.pad(v.reshape(b * h, tk, d), ((0, 0), (0, tk_pad - tk), (0, 0)))

    n_kblocks = tk_pad // bk
    grid = (b * h, tq_pad // bq, n_kblocks)
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, scale=scale, causal=causal,
        t_k_valid=tk, n_kblocks=n_kblocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out[:, :tq].reshape(b, h, tq, d)
