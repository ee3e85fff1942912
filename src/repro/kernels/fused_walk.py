"""Fused packed-plan walk — ONE Pallas launch per flush (DESIGN.md §12).

The ``executor='fused'`` kernels. Where the jnp ``packed`` executor runs the
canonical climb as a ``fori_loop`` of paired device gathers (one XLA gather
chain per level), these kernels run the ENTIRE walk — per-level node
selection, rank-state update, and the q_s window contraction — inside a
single ``pl.pallas_call``: the level loop is a static in-kernel unroll over
scratch-resident ``[TQ]`` rank state, the node reads collapse into one
selection-matrix MXU matmul against the edge's q_t-folded value block (the
gather-free formulation of ``dyn_query``), and each window's scalar leaves
the kernel already contracted. W rides inside the table row exactly as in
the packed layout, so one launch moves every window's answer.

Two kernels, matching the two packed table layouts:

  * :func:`fused_walk_pallas` — the canonical ≤2-nodes-per-level climb over
    q_t-folded node values. Serves BOTH the static RFS packed forest (per
    npad size class; level ℓ of an edge block holds ``npad >> ℓ`` nodes)
    and DRFS exact mode (complete tree, ``dyn_node_tables`` layout). The
    per-walk-level node offsets arrive as the static ``offs`` tuple, which
    is the only difference between the two layouts.
  * :func:`fused_leaf_pallas` — the quantized DRFS stacked-prefix gather
    with the window contraction fused in: the kernel takes the RAW spatial
    coefficients ``qs`` plus the small ``[W, k_t]`` temporal vectors and
    builds the q_s ⊗ q_t contraction in-register, instead of consuming the
    materialized [G, W, Q, K] query-vector tensors ``dyn_leaf_query_pallas``
    needs — the fused variant's input bytes scale with atoms, not
    atoms × windows.

``precise=True`` (the interpret-mode default) keeps the table dtype all the
way through so the ref.py oracles match bitwise in f64; ``precise=False``
casts inputs to f32 for a compiled TPU launch. Compressed ``TableCodec``
layouts simply arrive as f32/bf16 tables — the kernels run in the input
dtype either way.

TPU layout: the per-edge tables arrive feature-major ([G, W·C, R], node
axis minor, contracted as a transposed-RHS matmul), per-atom fields are
[TQ, 1] columns (``tree_query._col``), and the kernels write [TQ, W]
output tiles one window column at a time; the wrappers hand back the
[G, W, Q] contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tree_query import _HIGHEST, _col

__all__ = ["fused_walk_pallas", "fused_leaf_pallas"]


_NT = (((1,), (1,)), ((), ()))  # [TQ, R] x [WK, R] -> [TQ, WK]


def _fused_walk_kernel(nv_ref, rlo_ref, rhi_ref, side_ref, qs_ref, o_ref, *,
                       offs, nw, ks):
    TQ = o_ref.shape[1]
    R2 = nv_ref.shape[2]
    dt = nv_ref.dtype
    iota = jax.lax.broadcasted_iota(jnp.int32, (TQ, R2), 1)
    side = side_ref[0]  # [TQ, 1]
    l = rlo_ref[0]
    r = rhi_ref[0]
    hit = jnp.zeros((TQ, R2), jnp.bool_)
    # canonical ≤2-nodes-per-level climb, statically unrolled; walk level
    # ``lev`` reads the node block starting at row offs[lev] (node units)
    # of the per-edge value block — offs encodes the layout (RFS packed
    # level-major vs the DRFS complete tree), nothing else differs. Every
    # node is selected at most once, so the selection is a 0/1 mask.
    for off in offs:
        active = l < r
        emit_l = active & ((l & 1) == 1)
        hit = hit | (emit_l & (iota == (off + l) * 2 + side))
        l = jnp.where(emit_l, l + 1, l)
        emit_r = (l < r) & ((r & 1) == 1)
        hit = hit | (emit_r & (iota == (off + r - 1) * 2 + side))
        r = jnp.where(emit_r, r - 1, r)
        l, r = l >> 1, r >> 1
    # the whole walk in one matmul: [TQ, R2] x [W·2k_s, R2]^T
    acc = jax.lax.dot_general(hit.astype(dt), nv_ref[0], _NT, precision=_HIGHEST,
                              preferred_element_type=dt)
    qs = qs_ref[0]  # [TQ, k_s]
    for w in range(nw):
        b = w * 2 * ks
        v = acc[:, b : b + ks] + acc[:, b + ks : b + 2 * ks]
        o_ref[0, :, w : w + 1] = jnp.sum(qs * v, axis=1, keepdims=True)


def _pad_q(x, qp: int):
    """Pad axis 1 (the atom axis of [G, Q, ...]) to qp with zeros."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, qp - x.shape[1])
    return jnp.pad(x, pad)


def _atom_spec(tq: int, width: int = 1):
    """(1, tq, width) block over a [G, Qp, width] per-atom array."""
    return pl.BlockSpec((1, tq, width), lambda g, q: (g, q, 0))


@functools.partial(jax.jit, static_argnames=("offs", "tq", "interpret", "precise"))
def fused_walk_pallas(
    nodeval: jnp.ndarray,  # [G, W·2k_s, R2] per-edge q_t-folded node values
    r_lo: jnp.ndarray,  # [G, Q] root position-rank interval lo
    r_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q] event-feature side in {0, 1}
    qs: jnp.ndarray,  # [G, Q, k_s] spatial coefficient vectors
    *,
    offs: tuple,  # per-walk-level node-row offsets within the edge block
    tq: int = 128,
    interpret: bool = True,
    precise: bool = True,
) -> jnp.ndarray:
    """Fused canonical walk + window contraction: [G, W, Q], halves folded.

    ONE kernel launch answers every (atom, level, window) of the flush —
    the climb that the jnp executor spreads over ``len(offs)`` gather
    dispatches. ``offs[lev]`` is the first node row of walk level ``lev``
    inside each edge's value block (static per npad/depth class).
    """
    G, WC, R2 = nodeval.shape
    Q, ks = qs.shape[1], qs.shape[2]
    W = WC // (2 * ks)
    tq = min(tq, Q) or 1
    qp = -(-Q // tq) * tq
    if not precise:
        nodeval = nodeval.astype(jnp.float32)
        qs = qs.astype(jnp.float32)
    ints = [_col(_pad_q(x.astype(jnp.int32), qp)) for x in (r_lo, r_hi, side)]
    out = pl.pallas_call(
        functools.partial(_fused_walk_kernel, offs=tuple(offs), nw=W, ks=ks),
        grid=(G, qp // tq),
        in_specs=[
            pl.BlockSpec((1, WC, R2), lambda g, q: (g, 0, 0)),
            _atom_spec(tq), _atom_spec(tq), _atom_spec(tq),
            _atom_spec(tq, ks),
        ],
        out_specs=_atom_spec(tq, W),
        out_shape=jax.ShapeDtypeStruct((G, qp, W), nodeval.dtype),
        interpret=interpret,
    )(nodeval, *ints, _pad_q(qs.astype(nodeval.dtype), qp))
    return jnp.transpose(out[:, :Q], (0, 2, 1))


def _fused_leaf_kernel(tab_ref, llo_ref, lhi_ref, side_ref, qs_ref, qtl_ref,
                       qtr_ref, o_ref, *, nw, ks, kt):
    TQ = o_ref.shape[1]
    R = tab_ref.shape[2]
    dt = tab_ref.dtype
    kk = ks * kt
    iota = jax.lax.broadcasted_iota(jnp.int32, (TQ, R), 1)
    side = side_ref[0]  # [TQ, 1]
    oh = (
        (iota == lhi_ref[0] * 2 + side).astype(dt)
        - (iota == llo_ref[0] * 2 + side).astype(dt)
    )
    # prefix difference via one matmul: [TQ, R] x [W·2K, R]^T
    diff = jax.lax.dot_general(oh, tab_ref[0], _NT, precision=_HIGHEST,
                               preferred_element_type=dt)
    qs = qs_ref[0]  # [TQ, k_s]
    qtl = qtl_ref[...]  # [W, k_t]
    qtr = qtr_ref[...]
    for w in range(nw):
        # fused q_s ⊗ q_t contraction built IN-kernel: only raw q_s and the
        # tiny [W, k_t] temporal vectors cross the launch; feature j =
        # s·k_t + t of the moment row pairs with q_s[s]·q_t[t], the s-major
        # order of the jnp executor and the dyn_query kernels
        b = w * 2 * kk
        val = jnp.zeros((TQ, 1), dt)
        for s in range(ks):
            dl = diff[:, b + s * kt : b + (s + 1) * kt]  # [TQ, k_t]
            dr = diff[:, b + kk + s * kt : b + kk + (s + 1) * kt]
            qsv = qs[:, s : s + 1]
            val = val + jnp.sum(qsv * qtl[w : w + 1] * dl, axis=1, keepdims=True)
            val = val + jnp.sum(qsv * qtr[w : w + 1] * dr, axis=1, keepdims=True)
        o_ref[0, :, w : w + 1] = val


@functools.partial(jax.jit, static_argnames=("tq", "interpret", "precise"))
def fused_leaf_pallas(
    lcum: jnp.ndarray,  # [G, W·2K, (nleaf+1)·2] per-edge leaf-prefix tables
    leaf_lo: jnp.ndarray,  # [G, Q] fully-covered leaf range lo (i32)
    leaf_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q]
    qs: jnp.ndarray,  # [G, Q, k_s] spatial coefficient vectors
    qtl: jnp.ndarray,  # [W, k_t] left-half temporal vectors
    qtr: jnp.ndarray,  # [W, k_t] right-half temporal vectors
    *,
    tq: int = 128,
    interpret: bool = True,
    precise: bool = True,
) -> jnp.ndarray:
    """Fused quantized DRFS tree phase: [G, W, Q], halves folded.

    The stacked-prefix gather of ``dyn_leaf_query_pallas`` with the window
    contraction fused into the launch: inputs are the raw per-atom q_s and
    the tiny per-window q_t tables instead of the atoms × windows query
    tensors.
    """
    G, WK, R = lcum.shape
    Q, ks = qs.shape[1], qs.shape[2]
    W, kt = qtl.shape[0], qtl.shape[1]
    tq = min(tq, Q) or 1
    qp = -(-Q // tq) * tq
    if not precise:
        lcum = lcum.astype(jnp.float32)
        qs = qs.astype(jnp.float32)
        qtl = qtl.astype(jnp.float32)
        qtr = qtr.astype(jnp.float32)
    ints = [_col(_pad_q(x.astype(jnp.int32), qp)) for x in (leaf_lo, leaf_hi, side)]
    out = pl.pallas_call(
        functools.partial(_fused_leaf_kernel, nw=W, ks=ks, kt=kt),
        grid=(G, qp // tq),
        in_specs=[
            pl.BlockSpec((1, WK, R), lambda g, q: (g, 0, 0)),
            _atom_spec(tq), _atom_spec(tq), _atom_spec(tq),
            _atom_spec(tq, ks),
            pl.BlockSpec((W, kt), lambda g, q: (0, 0)),
            pl.BlockSpec((W, kt), lambda g, q: (0, 0)),
        ],
        out_specs=_atom_spec(tq, W),
        out_shape=jax.ShapeDtypeStruct((G, qp, W), lcum.dtype),
        interpret=interpret,
    )(
        lcum, *ints, _pad_q(qs.astype(lcum.dtype), qp),
        qtl.astype(lcum.dtype), qtr.astype(lcum.dtype),
    )
    return jnp.transpose(out[:, :Q], (0, 2, 1))
