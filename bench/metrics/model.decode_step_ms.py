"""Mean device time of one run of the paged decode-step program (trace)."""
from harness import readers


def read(run):
    return readers.program_ms(run, readers.DECODE_PROGRAM)
