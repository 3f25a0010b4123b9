"""Host time of ``KernelFn.make_request`` per launch (program span
``cox.resolve``: compile lookup, knob resolution, argument binding, the
schedule verdict, the autotune lookup), in the cells that chain dependent
launches between syncs, in ms."""

from bench import program_spans


def read(run):
    pt = program_spans.of(run)
    return None if pt is None else pt.mean_ms("cox.resolve")
