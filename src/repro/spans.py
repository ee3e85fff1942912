"""Names of the program's trace spans, and the one helper that opens them.

Every span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
own trace, on the clock of the device planes, while a profiler session is
active, and costs one cheap check otherwise (DESIGN.md §10). Serve spans
carry ``flush=<id>``, the ``RequestStats.flush_id`` of the requests the
flush answered.
"""
from __future__ import annotations

import contextlib
import sys

SPANS = ("serve.dispatch", "tnkde.plan", "tnkde.window_batch", "tnkde.enqueue",
         "serve.retire", "tnkde.transfer", "tnkde.ls_sweep", "serve.assemble")
# the sharded engines' (DESIGN.md §3): the host slab build and upload of an
# index epoch, at construction and at the first query of a new epoch
SHARD_SPANS = ("shard.slab",)


def span(name: str, **ids):
    """A trace span named ``name`` with ``ids`` as its metadata."""
    jax = sys.modules.get("jax")
    if jax is None:  # no JAX in the process, so no profiler can be tracing
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **ids)
