"""The paged flash-decode kernel's roofline time for the traced steps over its device time, in percent."""
from harness import readers


def read(run):
    return readers.kernel_roofline_pct(run, "paged_decode")
