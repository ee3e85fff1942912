"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults from the backend of the computation
(``repro.compat.pallas_interpret``): on CPU the kernel body executes step by
step (semantics identical to TPU); on a TPU the kernel is compiled. Pass
``interpret=`` explicitly to override.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.compat import pallas_interpret

from .dyn_query import dyn_leaf_query_pallas, dyn_node_walk_pallas
from .flash_attention import flash_attention_pallas
from .fused_walk import fused_leaf_pallas, fused_walk_pallas
from .minplus import minplus_matmul_pallas
from .tree_query import tree_query_pallas

__all__ = [
    "minplus_matmul",
    "tree_query",
    "dyn_leaf_query",
    "dyn_node_walk",
    "fused_walk",
    "fused_leaf",
    "flash_attention",
]


def minplus_matmul(a: jnp.ndarray, b: jnp.ndarray, **kw) -> jnp.ndarray:
    kw.setdefault("interpret", pallas_interpret(a))
    return minplus_matmul_pallas(a, b, **kw)


def tree_query(*args, **kw) -> jnp.ndarray:
    """Window-batched merge-tree range query: rank bounds / q_vec carry a
    [G, W, Q] window axis; position bounds stay [G, Q] (see tree_query.py)."""
    kw.setdefault("interpret", pallas_interpret(args[0] if args else None))
    return tree_query_pallas(*args, **kw)


def dyn_leaf_query(*args, **kw) -> jnp.ndarray:
    """Quantized DRFS tree phase over per-edge leaf-prefix tables (see
    dyn_query.py): [G, W, Q], halves folded per window center."""
    kw.setdefault("interpret", pallas_interpret(args[0] if args else None))
    return dyn_leaf_query_pallas(*args, **kw)


def dyn_node_walk(*args, **kw) -> jnp.ndarray:
    """Exact-mode DRFS tree phase over q_t-folded per-edge node values (see
    dyn_query.py): [G, W, Q], halves folded per window center."""
    kw.setdefault("interpret", pallas_interpret(args[0] if args else None))
    return dyn_node_walk_pallas(*args, **kw)


def fused_walk(*args, **kw) -> jnp.ndarray:
    """Fused packed-plan walk: one launch runs the whole canonical climb +
    window contraction (see fused_walk.py): [G, W, Q], halves folded."""
    kw.setdefault("interpret", pallas_interpret(args[0] if args else None))
    return fused_walk_pallas(*args, **kw)


def fused_leaf(*args, **kw) -> jnp.ndarray:
    """Fused quantized DRFS leaf-prefix query (see fused_walk.py):
    [G, W, Q], halves folded per window center."""
    kw.setdefault("interpret", pallas_interpret(args[0] if args else None))
    return fused_leaf_pallas(*args, **kw)


def flash_attention(q, k, v, **kw) -> jnp.ndarray:
    kw.setdefault("interpret", pallas_interpret(q))
    return flash_attention_pallas(q, k, v, **kw)
