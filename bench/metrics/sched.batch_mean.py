"""Decoded tokens per decode step over the window (scheduler)."""
from harness import readers


def read(run):
    return readers.batch_mean(run)
