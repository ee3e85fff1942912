"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips."""
from harness.layer import idle_share


def read(run):
    return idle_share(run)
