"""The one module that knows about JAX versions, x64 and the backend.

Everything that touches a mesh, ``shard_map``, the 64-bit switch, the
device path's numeric precision, Pallas interpret mode or the persistent
compilation cache goes through here, so version and backend drift is
handled in exactly one place. Targets the installed JAX (>= 0.9).
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterable, Optional, Sequence

import jax

__all__ = [
    "make_mesh",
    "host_mesh",
    "shard_map",
    "x64",
    "device_x64",
    "device_precision",
    "pallas_interpret",
    "enable_compile_cache",
]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def host_mesh(n_shards: int, axes: Sequence[str] = ("data",)):
    """A mesh over the FIRST ``n_shards`` local devices.

    ``jax.make_mesh`` insists on using every visible device; the sharded-KDE
    tests force 8 host devices and then want 2- and 4-shard meshes in the
    same process, so this builds a plain Mesh over a device prefix instead.
    Multi-axis shapes fold the prefix row-major (axes[0] outermost).
    """
    import numpy as np

    if isinstance(n_shards, int):
        shape = (n_shards,)
    else:
        shape = tuple(n_shards)
    total = 1
    for s in shape:
        total *= int(s)
    devs = jax.devices()
    if total > len(devs):
        raise ValueError(f"host_mesh needs {total} devices, have {len(devs)}")
    arr = np.array(devs[:total]).reshape(shape)
    return jax.sharding.Mesh(arr, tuple(axes))


def shard_map(
    f,
    *,
    mesh,
    in_specs,
    out_specs,
    manual_axes: Optional[Iterable[str]] = None,
    check: bool = False,
):
    """``jax.shard_map``.

    manual_axes: axes the body handles manually (None = all mesh axes).
    check: replication/VMA checking (off by default — the bodies here use
    ``psum`` on hand-specified specs the checker cannot always follow).
    """
    kw = {"check_vma": check}
    if manual_axes is not None:
        kw["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)


def x64(enabled: bool = True):
    """Scope in which 64-bit types are (or are not) enabled."""
    return jax.enable_x64(bool(enabled))


def device_x64() -> bool:
    """Whether the device path computes in 64 bits.

    Only on the CPU backend, where f64 is native and the engines must agree
    with the NumPy oracles to <= 1e-12. An accelerator's compiler emulates
    f64 (a v5e compile of a small f64 gather + cumsum did not finish in
    minutes), so there the tables, window values and heatmaps are f32.
    Order comparisons stay exact either way: event and window times reach
    the device as order-preserving int32 key pairs (``jax_engine.time_key``).
    """
    return jax.default_backend() == "cpu"


@contextlib.contextmanager
def device_precision():
    """The numeric scope every device-path upload and jit call runs in.

    CPU: x64 on, so host float64 arrays stay float64 on the device.
    Accelerator: x64 off (uploads canonicalize to f32/int32) and f32
    matmuls/einsums at full precision instead of the default one-pass
    bf16, so f32 is the only rounding the chip adds.
    """
    if device_x64():
        with jax.enable_x64(True):
            yield
    else:
        with jax.enable_x64(False), jax.default_matmul_precision("highest"):
            yield


def pallas_interpret(x=None) -> bool:
    """Whether a Pallas kernel must run in interpret mode.

    Derived from the backend of the computation: the platform of ``x``'s
    device when ``x`` is a committed device array, else the default backend.
    Interpret (the kernel body executed step by step) only on CPU; on a TPU
    the kernel is compiled.
    """
    devices = getattr(x, "devices", None)
    if callable(devices):
        try:
            platforms = {d.platform for d in devices()}
        except Exception:  # a tracer: no device of its own
            platforms = set()
        if platforms:
            return platforms == {"cpu"}
    return jax.default_backend() == "cpu"


def enable_compile_cache(repo_root: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
    and nothing else is set here. Otherwise the cache lives at the fixed
    path ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part
    of the cache key, so it must never depend on a temp dir, pid or time.
    Call this from entry points only (scripts, ``main``), never at import
    time of a library module.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = env
    else:
        if repo_root is None:
            here = os.path.dirname(os.path.abspath(__file__))
            repo_root = os.path.dirname(os.path.dirname(here))
        path = os.path.join(repo_root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick to compile: a chip call starts cold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
