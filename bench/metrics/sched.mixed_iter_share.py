"""Share of the window's decode iterations that also carried prefill-side work, in percent (scheduler)."""
from harness import program_spans


def read(run):
    return program_spans.mixed_share_pct(run)
