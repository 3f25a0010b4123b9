"""Host time of the dispatcher's ``_dispatch`` per launch (program span
``cox.dispatch``: from the dispatcher taking the request to XLA's async
call returning, with the stage lookup, the transfer node and the
telemetry), in the cells that chain dependent launches between syncs, in
ms."""

from bench import program_spans


def read(run):
    pt = program_spans.of(run)
    return None if pt is None else pt.mean_ms("cox.dispatch")
