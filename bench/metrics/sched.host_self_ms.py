"""Host self time of a decode iteration, mean over the window, in ms (scheduler)."""
from harness import program_spans


def read(run):
    return program_spans.host_self_ms(run)
