"""Merge-tree range query — Pallas TPU kernel (the RFS/DRFS inner loop).

This is the paper's Algorithm 2 (DualDetect) after the hardware adaptation of
DESIGN.md §2: per (edge-group g, query q, window w), canonically decompose
the time-rank interval [r_lo, r_hi) into <= 2 buckets per level and, inside
each bucket, select a position interval and dot the prefix-moment difference
with the query vector.

TPU-native choices (vs the CPU pointer walk):
  * the per-bucket *binary search* becomes a **masked compare-count**:
    rank(v) = Σ_j [j in bucket][p_row[j] (<|<=) v] — a VPU comparison
    -reduction over the VMEM-resident level row. No data-dependent control
    flow, no gather.
  * the *prefix-moment gather* becomes a **one-hot × table matmul** on the
    MXU: onehot(i-1) @ cum_level  ([TQ, NPAD] @ [NPAD, K]).
  * one grid step owns one edge-group's whole table (BlockSpec brings
    [LVL, NPAD(, K)] into VMEM) and a TQ-tile of its queries; the level
    loop is an in-kernel ``fori_loop`` (a static unroll of levels × windows
    did not compile in minutes at NPAD = 2048) and the window loop a static
    unroll inside it.

Window batching (DESIGN.md §4): the W axis carries the per-window time-rank
intervals and temporal-weighted query vectors; the position bounds are per
query only. Per level the three compare masks are computed **once** and
shared by every window — each window then pays only the masked in-bucket
counts and one signed one-hot prefix-moment matmul for each of its <= 2
buckets. That is the hoist that makes the per-window cost shrink as W
grows.

Callers bucket edges into groups of uniform padded size NPAD (size-classed
batching) — see repro.core.distributed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tree_query_pallas"]


_HIGHEST = jax.lax.Precision.HIGHEST  # f32 one-hot selections stay exact


def _col(x):
    """[G, Q] → [G, Q, 1]. Per-atom fields enter the kernels as (1, tq, 1)
    blocks, so each is a [TQ, 1] column that broadcasts against [TQ, R]
    tiles: the TPU tiling refuses a (1, tq) block over [G, Q], and Mosaic
    cannot recast an in-kernel (TQ,) vector to (TQ, 1)."""
    return x[..., None]


def _kernel(pos_ref, cum_ref, rlo_ref, rhi_ref, bnd_ref, l1r_ref, qv_ref, o_ref, *, lvl, npad, nw):
    TQ = o_ref.shape[1]
    dt = cum_ref.dtype  # f32 on TPU; f64 when the engine runs interpret mode
    f32 = jnp.float32
    iota = jax.lax.broadcasted_iota(jnp.int32, (TQ, npad), 1)  # [TQ, NPAD]
    bnd = bnd_ref[0]  # [TQ, 3] position bounds (hi, lo1, lo2)
    ph, pl1, pl2 = bnd[:, 0:1], bnd[:, 1:2], bnd[:, 2:3]  # [TQ, 1] columns
    l1r = l1r_ref[0] != 0
    qvs = [qv_ref[0, w] for w in range(nw)]  # each [TQ, K]

    def level(lev, carry):
        ls, rs, accs = (list(c) for c in carry)
        pr = pos_ref[0, pl.ds(lev, 1), :]  # [1, NPAD]
        c_lvl = cum_ref[0, lev]  # [NPAD, K]
        # ---- window-independent: compare masks (hoisted over windows) -----
        m_hi = (pr <= ph).astype(f32)  # [TQ, NPAD]
        m_l1 = jnp.where(l1r, (pr <= pl1).astype(f32), (pr < pl1).astype(f32))
        m_l2 = (pr < pl2).astype(f32)
        bucket = iota >> lev

        def bucket_val(b, on, qv):
            inb = (bucket == b).astype(f32)  # bucket b's slots of the row
            seg_lo = b << lev
            i_hi = seg_lo + jnp.sum(inb * m_hi, axis=1, keepdims=True).astype(jnp.int32)
            c_l1 = jnp.sum(inb * m_l1, axis=1, keepdims=True).astype(jnp.int32)
            c_l2 = jnp.sum(inb * m_l2, axis=1, keepdims=True).astype(jnp.int32)
            i_lo = seg_lo + jnp.maximum(c_l1, c_l2)
            i_hi = jnp.maximum(i_hi, i_lo)
            # prefix(i_hi) − prefix(i_lo) in ONE signed one-hot matmul (MXU)
            oh = (
                ((iota == i_hi - 1) & (i_hi > seg_lo)).astype(dt)
                - ((iota == i_lo - 1) & (i_lo > seg_lo)).astype(dt)
            )
            mom = jnp.dot(oh, c_lvl, precision=_HIGHEST, preferred_element_type=dt)
            return jnp.where(on, jnp.sum(qv * mom, axis=1, keepdims=True), 0.0)

        # ---- per-window: canonical climb over the shared masks --------------
        for w in range(nw):
            l, r = ls[w], rs[w]
            active = l < r
            emit_l = active & ((l & 1) == 1)
            accs[w] = accs[w] + bucket_val(l, emit_l, qvs[w])
            l = jnp.where(emit_l, l + 1, l)
            emit_r = (l < r) & ((r & 1) == 1)
            accs[w] = accs[w] + bucket_val(r - 1, emit_r, qvs[w])
            r = jnp.where(emit_r, r - 1, r)
            ls[w], rs[w] = l >> 1, r >> 1
        return tuple(ls), tuple(rs), tuple(accs)

    init = (
        tuple(rlo_ref[0, :, w : w + 1] for w in range(nw)),  # each [TQ, 1]
        tuple(rhi_ref[0, :, w : w + 1] for w in range(nw)),
        tuple(jnp.zeros((TQ, 1), dt) for _ in range(nw)),
    )
    _, _, accs = jax.lax.fori_loop(0, lvl, level, init)
    for w in range(nw):
        o_ref[0, :, w : w + 1] = accs[w]


@functools.partial(jax.jit, static_argnames=("tq", "interpret", "precise"))
def tree_query_pallas(
    pos: jnp.ndarray,  # [G, LVL, NPAD] f32 (+inf padded)
    cum: jnp.ndarray,  # [G, LVL, NPAD, K] f32
    r_lo: jnp.ndarray,  # [G, W, Q] i32 per-window time-rank interval lo
    r_hi: jnp.ndarray,  # [G, W, Q] i32
    pos_hi: jnp.ndarray,  # [G, Q] f32 (window-independent position bounds)
    pos_lo1: jnp.ndarray,  # [G, Q] f32
    lo1_right: jnp.ndarray,  # [G, Q] bool / i32
    pos_lo2: jnp.ndarray,  # [G, Q] f32
    q_vec: jnp.ndarray,  # [G, W, Q, K] f32
    *,
    tq: int = 128,
    interpret: bool = True,
    precise: bool = False,
) -> jnp.ndarray:
    """Window-batched merge-tree range query: [G, W, Q].

    ``precise=True`` keeps the input dtype (float64 interpret mode — the
    engine executor path, bit-comparable to the NumPy oracle); the default
    casts to float32, the TPU-compiled layout.
    """
    G, LVL, NPAD = pos.shape
    K = cum.shape[-1]
    W, Q = r_lo.shape[1], r_lo.shape[2]
    ft = pos.dtype if precise else jnp.float32
    tq = min(tq, Q) or 1
    qp = -(-Q // tq) * tq

    def padq(x):  # pad axis 1 (atoms) of [G, Q, ...]
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, qp - Q)
        return jnp.pad(x, pad)

    def win_major(x):  # [G, W, Q] -> [G, Qp, W] i32
        return padq(jnp.transpose(x.astype(jnp.int32), (0, 2, 1)))

    bounds = jnp.stack(
        [pos_hi.astype(ft), pos_lo1.astype(ft), pos_lo2.astype(ft)],
        axis=-1,
    )
    qv = jnp.pad(q_vec.astype(ft), ((0, 0), (0, 0), (0, qp - Q), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, lvl=LVL, npad=NPAD, nw=W),
        grid=(G, qp // tq),
        in_specs=[
            pl.BlockSpec((1, LVL, NPAD), lambda g, q: (g, 0, 0)),
            pl.BlockSpec((1, LVL, NPAD, K), lambda g, q: (g, 0, 0, 0)),
            pl.BlockSpec((1, tq, W), lambda g, q: (g, q, 0)),
            pl.BlockSpec((1, tq, W), lambda g, q: (g, q, 0)),
            pl.BlockSpec((1, tq, 3), lambda g, q: (g, q, 0)),
            pl.BlockSpec((1, tq, 1), lambda g, q: (g, q, 0)),
            pl.BlockSpec((1, W, tq, K), lambda g, q: (g, 0, q, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, W), lambda g, q: (g, q, 0)),
        out_shape=jax.ShapeDtypeStruct((G, qp, W), ft),
        interpret=interpret,
    )(
        pos.astype(ft),
        cum.astype(ft),
        win_major(r_lo),
        win_major(r_hi),
        padq(bounds),
        _col(padq(lo1_right.astype(jnp.int32))),
        qv,
    )
    return jnp.transpose(out[:, :Q], (0, 2, 1))
