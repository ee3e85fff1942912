"""Ask XLA for host devices before JAX starts (this module imports no JAX).

The BENCH_dist rung shards over two devices. On a CPU host, a script that
runs it calls :func:`request_host_devices` first, so the CPU backend comes
up with that many devices; single-device rungs use the first one. Once JAX
has started, the call changes nothing.
"""
import os
import sys


def request_host_devices(n: int) -> None:
    """Add ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    unless JAX is already imported or the flag is already set."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={int(n)}".strip()
        )
