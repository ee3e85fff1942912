"""DRFS packed-plan query — Pallas TPU kernels (the dynamic inner loops).

The ``tree_query`` kernel family extended to the two DRFS table layouts of
the packed query plan (DESIGN.md §5/§7), giving ``solution='drfs'`` a kernel
path:

  * :func:`dyn_leaf_query_pallas` — the quantized serving mode over the
    **leaf-prefix layout** (``jax_engine.dyn_window_tables``): per edge a
    feature-major [W·2K, (nleaf+1)·2] table of per-side leaf-prefix moment
    columns (raw Φ, halves paired, the W axis inside the column). An atom's
    fully-covered leaf range costs one signed one-hot selection (an MXU
    matmul — the gather-free formulation) and one contraction with the
    per-half query vectors.
  * :func:`dyn_node_walk_pallas` — the exact mode over the **node-value
    layout** (``jax_engine.dyn_node_tables`` repacked per edge): the
    canonical ≤2-nodes-per-level walk of ``fused_walk_pallas`` with the
    complete-tree row offsets — one [TQ, R] selection mask over the static
    level unroll and ONE matmul against the q_t-folded node table.

Both kernels cover phase 1 (the tree) of ``jax_engine.eval_atoms_dyn``; the
partial-leaf and pending scans stay in the surrounding jit (they are masked
fixed-trip loops with no reuse for the MXU). Callers group atoms by event
edge — one grid step owns one edge's table block and a TQ-tile of its atoms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_walk import _NT, _atom_spec, _pad_q, fused_walk_pallas
from .tree_query import _HIGHEST, _col

__all__ = ["dyn_leaf_query_pallas", "dyn_node_walk_pallas"]


def _leaf_kernel(tab_ref, llo_ref, lhi_ref, side_ref, qvl_ref, qvr_ref, o_ref, *, nw, kk):
    TQ = o_ref.shape[1]
    R = tab_ref.shape[2]
    dt = tab_ref.dtype
    iota = jax.lax.broadcasted_iota(jnp.int32, (TQ, R), 1)
    side = side_ref[0]  # [TQ, 1]
    oh = (
        (iota == lhi_ref[0] * 2 + side).astype(dt)
        - (iota == llo_ref[0] * 2 + side).astype(dt)
    )
    # prefix difference via one matmul: [TQ, R] x [W·2K, R]^T
    diff = jax.lax.dot_general(oh, tab_ref[0], _NT, precision=_HIGHEST,
                               preferred_element_type=dt)
    for w in range(nw):
        b = w * 2 * kk
        qvl = qvl_ref[0, w]  # [TQ, K]
        qvr = qvr_ref[0, w]
        o_ref[0, :, w : w + 1] = (
            jnp.sum(qvl * diff[:, b : b + kk], axis=1, keepdims=True)
            + jnp.sum(qvr * diff[:, b + kk : b + 2 * kk], axis=1, keepdims=True)
        )


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def dyn_leaf_query_pallas(
    tab: jnp.ndarray,  # [G, W·2K, (nleaf+1)·2] per-edge leaf-prefix tables
    leaf_lo: jnp.ndarray,  # [G, Q] fully-covered leaf range lo (i32)
    leaf_hi: jnp.ndarray,  # [G, Q] leaf range hi
    side: jnp.ndarray,  # [G, Q] event-feature side in {0, 1}
    qv_l: jnp.ndarray,  # [G, W, Q, K] left-half query vectors (q_s ⊗ q_t)
    qv_r: jnp.ndarray,  # [G, W, Q, K] right-half query vectors
    *,
    tq: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Quantized DRFS tree phase over the leaf-prefix layout: [G, W, Q],
    halves already folded per window center. Runs in the input dtype."""
    G, WK, R = tab.shape
    W, Q, K = qv_l.shape[1], qv_l.shape[2], qv_l.shape[3]
    tq = min(tq, Q) or 1
    qp = -(-Q // tq) * tq

    def padq_w(x):  # [G, W, Q, K]: pad the atom axis
        return jnp.pad(x.astype(tab.dtype), ((0, 0), (0, 0), (0, qp - Q), (0, 0)))

    ints = [_col(_pad_q(x.astype(jnp.int32), qp)) for x in (leaf_lo, leaf_hi, side)]
    out = pl.pallas_call(
        functools.partial(_leaf_kernel, nw=W, kk=K),
        grid=(G, qp // tq),
        in_specs=[
            pl.BlockSpec((1, WK, R), lambda g, q: (g, 0, 0)),
            _atom_spec(tq), _atom_spec(tq), _atom_spec(tq),
            pl.BlockSpec((1, W, tq, K), lambda g, q: (g, 0, q, 0)),
            pl.BlockSpec((1, W, tq, K), lambda g, q: (g, 0, q, 0)),
        ],
        out_specs=_atom_spec(tq, W),
        out_shape=jax.ShapeDtypeStruct((G, qp, W), tab.dtype),
        interpret=interpret,
    )(tab, *ints, padq_w(qv_l), padq_w(qv_r))
    return jnp.transpose(out[:, :Q], (0, 2, 1))


@functools.partial(jax.jit, static_argnames=("hq", "tq", "interpret"))
def dyn_node_walk_pallas(
    nodeval: jnp.ndarray,  # [G, W·2k_s, (2^{hq+1}−1)·2] per-edge node values
    r_lo: jnp.ndarray,  # [G, Q] fully-covered leaf range lo
    r_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q]
    qs: jnp.ndarray,  # [G, Q, k_s] spatial coefficient vectors
    *,
    hq: int,
    tq: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Exact-mode DRFS tree phase over q_t-folded node values: [G, W, Q],
    halves folded. Walk level ``lev`` reads depth d = hq − lev, whose
    within-edge block starts at node row 2^d − 1 (the per-edge repack of
    ``dyn_node_tables``); runs in the input dtype."""
    offs = tuple((1 << (hq - lev)) - 1 for lev in range(hq + 1))
    return fused_walk_pallas(
        nodeval, r_lo, r_hi, side, qs,
        offs=offs, tq=tq, interpret=interpret, precise=True,
    )
