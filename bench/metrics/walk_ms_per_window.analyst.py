"""Device time of the walk programs (the flushes) per heat row computed."""
from harness.layer import device_ms_per_row

PROGRAMS = ("_flush",)


def read(run):
    return device_ms_per_row(run, PROGRAMS)
