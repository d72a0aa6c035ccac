"""Roofline time of the traced decode steps and prefill chunks over the traced window, in percent."""
from harness import readers


def read(run):
    return readers.step_mfu_pct(run)
