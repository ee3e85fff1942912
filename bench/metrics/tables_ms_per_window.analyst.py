"""Device time of the window-table programs per heat row computed."""
from harness.layer import device_ms_per_row

PROGRAMS = ("packed_node_tables", "packed_root_ranks", "dyn_window_tables",
            "dyn_node_tables")


def read(run):
    return device_ms_per_row(run, PROGRAMS)
