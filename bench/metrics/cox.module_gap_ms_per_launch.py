"""Device-clock time between queued COX programs: from the end of one
``jit_cox_*`` XLA module to the start of the next on the same chip,
summed over the window and averaged over chips
(``bench.program_spans.cox_module_gaps_s``), per launch, in ms.  Set
against ``device_idle.cox``, it says whether the idle time lies between
the programs or inside them."""

from bench import program_spans


def read(run):
    pt = program_spans.of(run)
    launches = run.counters.get("launches")
    gaps = None if pt is None else program_spans.cox_module_gaps_s(pt)
    return None if gaps is None or not launches else 1e3 * gaps / launches
