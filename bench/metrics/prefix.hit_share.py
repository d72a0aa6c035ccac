"""Share of the window's prompt tokens served from the prefix cache, in percent (pager)."""
from harness import readers


def read(run):
    return readers.prefix_hit_pct(run)
