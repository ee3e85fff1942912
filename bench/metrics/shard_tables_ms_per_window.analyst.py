"""Per-chip device time of the sharded DRFS window-table program per heat
row computed (the trace reducer averages program time over the chips)."""
from harness.layer import device_ms_per_row

PROGRAMS = ("dyn_tables",)


def read(run):
    return device_ms_per_row(run, PROGRAMS)
