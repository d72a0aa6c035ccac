"""The flash kernel's roofline time for the traced chunks over its device time, in percent."""
from harness import readers


def read(run):
    return readers.kernel_roofline_pct(run, "flash_prefill")
