"""Per-chip device time of the sharded DRFS flush program (each shard's
walk and the psum of its heat delta) per heat row computed."""
from harness.layer import device_ms_per_row

PROGRAMS = ("dyn_flush",)


def read(run):
    return device_ms_per_row(run, PROGRAMS)
