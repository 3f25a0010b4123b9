"""Host work between decode steps, which the chip waits on at batch 1:
from the end of one step's ``serve.step.wait`` to the start of the
next's, mean over the window's pairs of steps of one request
(``bench.program_spans.decode_gaps_ms``), in ms."""

from bench import program_spans


def read(run):
    pt = program_spans.of(run)
    gaps = [] if pt is None else program_spans.decode_gaps_ms(pt)
    return sum(gaps) / len(gaps) if gaps else None
