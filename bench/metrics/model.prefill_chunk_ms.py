"""Mean device time of one run of the prefill-chunk program (trace)."""
from harness import readers


def read(run):
    return readers.program_ms(run, readers.CHUNK_PROGRAM)
