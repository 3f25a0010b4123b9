"""Host time of a decode step's call (program span ``serve.step.call``:
the token and position arrays, and the jitted step's Python dispatch,
which flattens the parameter tree), per decode step, in ms."""

from bench import program_spans


def read(run):
    pt = program_spans.of(run)
    return None if pt is None else pt.mean_ms("serve.step.call")
