"""1 minus the union of device-busy intervals over the traced window."""
UNIT = "%"


def read(run):
    return 100.0 * run["trace"].idle_share
