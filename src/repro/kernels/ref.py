"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the semantic ground truth the Pallas kernels are tested
against with ``interpret=True`` shape/dtype sweeps (tests/test_kernels_*).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "minplus_matmul",
    "tree_query",
    "dyn_leaf_query",
    "dyn_node_walk",
    "fused_walk",
    "fused_leaf",
    "flash_attention",
]


def minplus_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(min, +) matrix product: out[i, j] = min_k a[i, k] + b[k, j].

    The relaxation step of batched multi-source Bellman-Ford
    (repro.core.shortest_path.minplus_bellman_ford).
    """
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def tree_query(
    pos: jnp.ndarray,  # [G, LVL, NPAD] position-sorted bucket tables (+inf pad)
    cum: jnp.ndarray,  # [G, LVL, NPAD, K] inclusive per-bucket prefix moments
    r_lo: jnp.ndarray,  # [G, W, Q] per-window time-rank interval lo
    r_hi: jnp.ndarray,  # [G, W, Q] time-rank interval hi
    pos_hi: jnp.ndarray,  # [G, Q] upper position bound (inclusive, 'right')
    pos_lo1: jnp.ndarray,  # [G, Q] lower bound 1
    lo1_right: jnp.ndarray,  # [G, Q] bool: lower bound 1 is exclusive ('right')
    pos_lo2: jnp.ndarray,  # [G, Q] lower bound 2 (inclusive, 'left')
    q_vec: jnp.ndarray,  # [G, W, Q, K] query coefficient vectors
) -> jnp.ndarray:
    """Window-batched merge-tree range query (the RFS inner loop, Alg. 2).

    For each (window, query): canonically decompose the rank interval
    [r_lo, r_hi) over the level-ℓ buckets (size 2^ℓ, level ℓ stored at
    pos[:, ℓ]); inside each emitted bucket select events with position in
    (lo, hi] bounds via binary search and dot the prefix-moment difference
    with q_vec. The position bounds are shared by all W windows (only the
    rank interval and query vector carry a window axis). Returns [G, W, Q].
    """
    G, LVL, NPAD = pos.shape
    K = cum.shape[-1]

    def search(p_row, lo, hi, val, right):
        # binary search in p_row[lo:hi] (ascending), fixed trip count
        def body(_, lh):
            l, h = lh
            m = (l + h) // 2
            v = p_row[m]
            go = jnp.where(right, v <= val, v < val) & (l < h)
            return jnp.where(go, m + 1, l), jnp.where(go | (l >= h), h, m)

        steps = max(int(NPAD).bit_length(), 1)
        l, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
        return l

    def one_group(p_g, c_g, rl_g, rh_g, ph_g, pl1_g, l1r_g, pl2_g, qv_g):
        def one_query(rl, rh, ph, pl1, l1r, pl2, qv):
            def level_body(lev, state):
                l, r, acc = state
                p_row = jax.lax.dynamic_index_in_dim(p_g, lev, 0, keepdims=False)
                c_lvl = jax.lax.dynamic_index_in_dim(c_g, lev, 0, keepdims=False)

                def bucket_val(b, on):
                    seg_lo = b << lev
                    seg_hi = seg_lo + (1 << lev)
                    seg_hi = jnp.minimum(seg_hi, NPAD)
                    i_hi = search(p_row, seg_lo, seg_hi, ph, True)
                    i_l1 = search(p_row, seg_lo, seg_hi, pl1, l1r)
                    i_l2 = search(p_row, seg_lo, seg_hi, pl2, False)
                    i_lo = jnp.maximum(i_l1, i_l2)
                    i_hi = jnp.maximum(i_hi, i_lo)

                    def pref(i):
                        v = c_lvl[jnp.maximum(i - 1, 0)]
                        return jnp.where(i > seg_lo, v, jnp.zeros((K,), c_lvl.dtype))

                    mom = pref(i_hi) - pref(i_lo)
                    return jnp.where(on, qv @ mom, 0.0)

                active = l < r
                emit_l = active & ((l & 1) == 1)
                acc = acc + bucket_val(l, emit_l)
                l2 = jnp.where(emit_l, l + 1, l)
                emit_r = (l2 < r) & ((r & 1) == 1)
                acc = acc + bucket_val(r - 1, emit_r)
                r2 = jnp.where(emit_r, r - 1, r)
                return l2 >> 1, r2 >> 1, acc

            _, _, acc = jax.lax.fori_loop(
                0, LVL, level_body, (rl.astype(jnp.int32), rh.astype(jnp.int32), 0.0)
            )
            return acc

        def per_window(rl_w, rh_w, qv_w):
            return jax.vmap(one_query)(rl_w, rh_w, ph_g, pl1_g, l1r_g, pl2_g, qv_w)

        return jax.vmap(per_window)(rl_g, rh_g, qv_g)

    return jax.vmap(one_group)(pos, cum, r_lo, r_hi, pos_hi, pos_lo1, lo1_right, pos_lo2, q_vec)


def dyn_leaf_query(
    tab: jnp.ndarray,  # [G, W·2K, (nleaf+1)·2] per-edge leaf-prefix tables
    leaf_lo: jnp.ndarray,  # [G, Q]
    leaf_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q] in {0, 1}
    qv_l: jnp.ndarray,  # [G, W, Q, K]
    qv_r: jnp.ndarray,  # [G, W, Q, K]
) -> jnp.ndarray:
    """Quantized DRFS tree phase over the leaf-prefix layout: [G, W, Q].

    Per (edge g, atom q): difference of the two leaf-prefix rows selected by
    the fully-covered leaf range (side-interleaved rows, halves paired in
    the last axis, W inside the row), contracted with the per-half query
    vectors and folded per window center.
    """
    tab = jnp.swapaxes(tab, 1, 2)  # kernels take [G, W·C, R]
    G, R, WK = tab.shape
    W, Q, K = qv_l.shape[1], qv_l.shape[2], qv_l.shape[3]
    gi = jnp.arange(G)[:, None]
    idx_hi = leaf_hi.astype(jnp.int32) * 2 + side.astype(jnp.int32)
    idx_lo = leaf_lo.astype(jnp.int32) * 2 + side.astype(jnp.int32)
    diff = (tab[gi, idx_hi] - tab[gi, idx_lo]).reshape(G, Q, W, 2 * K)
    vl = jnp.einsum("gqwk,gwqk->gwq", diff[..., :K], qv_l)
    vr = jnp.einsum("gqwk,gwqk->gwq", diff[..., K:], qv_r)
    return vl + vr


def dyn_node_walk(
    nodeval: jnp.ndarray,  # [G, W·2k_s, (2^{hq+1}−1)·2] per-edge node values
    r_lo: jnp.ndarray,  # [G, Q] fully-covered leaf range lo
    r_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q]
    qs: jnp.ndarray,  # [G, Q, k_s]
    *,
    hq: int,
) -> jnp.ndarray:
    """Exact-mode DRFS tree phase: canonical walk over q_t-folded node
    values, halves folded per window center: [G, W, Q]."""
    nodeval = jnp.swapaxes(nodeval, 1, 2)  # kernels take [G, W·C, R]
    G, R2, WC = nodeval.shape
    Q, ks = qs.shape[1], qs.shape[2]
    W = WC // (2 * ks)
    gi = jnp.arange(G)[:, None]
    l = r_lo.astype(jnp.int32)
    r = r_hi.astype(jnp.int32)
    side = side.astype(jnp.int32)
    acc = jnp.zeros((G, Q, WC), nodeval.dtype)
    for lev in range(hq + 1):
        off = (1 << (hq - lev)) - 1
        active = l < r
        emit_l = active & ((l & 1) == 1)
        acc = acc + jnp.where(
            emit_l[..., None], nodeval[gi, (off + l) * 2 + side], 0.0
        )
        l = jnp.where(emit_l, l + 1, l)
        emit_r = (l < r) & ((r & 1) == 1)
        acc = acc + jnp.where(
            emit_r[..., None],
            nodeval[gi, jnp.maximum(off + r - 1, 0) * 2 + side],
            0.0,
        )
        r = jnp.where(emit_r, r - 1, r)
        l, r = l >> 1, r >> 1
    acc = acc.reshape(G, Q, W, 2, ks)
    return jnp.einsum("gqwcs,gqs->gwq", acc, qs)


def fused_walk(
    nodeval: jnp.ndarray,  # [G, W·2k_s, R2] per-edge q_t-folded node values
    r_lo: jnp.ndarray,  # [G, Q] root rank interval lo
    r_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q]
    qs: jnp.ndarray,  # [G, Q, k_s]
    *,
    offs: tuple,  # per-walk-level node-row offsets within the edge block
) -> jnp.ndarray:
    """Fused canonical walk + contraction over an arbitrary level layout:
    [G, W, Q], halves folded per window center.

    The :func:`dyn_node_walk` climb generalized to the static ``offs``
    tuple — walk level ``lev`` reads node rows starting at ``offs[lev]``
    (node units) of the per-edge block — which serves both the RFS packed
    forest layout (level-major, offs[ℓ] = Σ_{j<ℓ} npad>>j) and the DRFS
    complete tree (offs[ℓ] = 2^{hq−ℓ} − 1).
    """
    nodeval = jnp.swapaxes(nodeval, 1, 2)  # kernels take [G, W·C, R]
    G, R2, WC = nodeval.shape
    Q, ks = qs.shape[1], qs.shape[2]
    W = WC // (2 * ks)
    gi = jnp.arange(G)[:, None]
    l = r_lo.astype(jnp.int32)
    r = r_hi.astype(jnp.int32)
    side = side.astype(jnp.int32)
    acc = jnp.zeros((G, Q, WC), nodeval.dtype)
    for off in offs:
        active = l < r
        emit_l = active & ((l & 1) == 1)
        acc = acc + jnp.where(
            emit_l[..., None],
            nodeval[gi, jnp.clip((off + l) * 2 + side, 0, R2 - 1)],
            0.0,
        )
        l = jnp.where(emit_l, l + 1, l)
        emit_r = (l < r) & ((r & 1) == 1)
        acc = acc + jnp.where(
            emit_r[..., None],
            nodeval[gi, jnp.clip((off + r - 1) * 2 + side, 0, R2 - 1)],
            0.0,
        )
        r = jnp.where(emit_r, r - 1, r)
        l, r = l >> 1, r >> 1
    acc = acc.reshape(G, Q, W, 2, ks)
    return jnp.einsum("gqwcs,gqs->gwq", acc, qs)


def fused_leaf(
    lcum: jnp.ndarray,  # [G, W·2K, (nleaf+1)·2] per-edge leaf-prefix tables
    leaf_lo: jnp.ndarray,  # [G, Q]
    leaf_hi: jnp.ndarray,  # [G, Q]
    side: jnp.ndarray,  # [G, Q]
    qs: jnp.ndarray,  # [G, Q, k_s]
    qtl: jnp.ndarray,  # [W, k_t] left-half temporal vectors
    qtr: jnp.ndarray,  # [W, k_t] right-half temporal vectors
) -> jnp.ndarray:
    """Quantized DRFS tree phase with the q_s ⊗ q_t contraction fused in:
    [G, W, Q], halves folded — :func:`dyn_leaf_query` semantics consuming
    the raw factored query instead of materialized per-window vectors."""
    lcum = jnp.swapaxes(lcum, 1, 2)  # kernels take [G, W·C, R]
    G, R, WK = lcum.shape
    Q, ks = qs.shape[1], qs.shape[2]
    W, kt = qtl.shape[0], qtl.shape[1]
    K = ks * kt
    gi = jnp.arange(G)[:, None]
    idx_hi = leaf_hi.astype(jnp.int32) * 2 + side.astype(jnp.int32)
    idx_lo = leaf_lo.astype(jnp.int32) * 2 + side.astype(jnp.int32)
    diff = (lcum[gi, idx_hi] - lcum[gi, idx_lo]).reshape(G, Q, W, 2, ks, kt)
    vl = jnp.einsum("gqwst,wt,gqs->gwq", diff[:, :, :, 0], qtl, qs)
    vr = jnp.einsum("gqwst,wt,gqs->gwq", diff[:, :, :, 1], qtr, qs)
    return vl + vr


def flash_attention(
    q: jnp.ndarray,  # [B, H, S, D]
    k: jnp.ndarray,  # [B, Hkv, S, D]
    v: jnp.ndarray,  # [B, Hkv, S, D]
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference attention (materializes logits; GQA via head grouping)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    scale = (D ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
