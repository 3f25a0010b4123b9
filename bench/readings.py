"""Readings that more than one per-layer metric takes, each metric for
the cells it lists: the reader files in ``bench/metrics/`` bind them to
their names."""

from bench import work


def host_ms_per_launch(run):
    """Host time of a COX launch: the harness span from the call into
    ``launch`` until it returns (resolution, enqueue, staging, dispatch;
    not completion), averaged over the window's launches, in ms."""
    return run.mean_span_ms("cox.launch")


def device_ms_per_launch(run):
    """Device time of the COX programs in the traced window (every XLA
    module there; the harness issues no device work of its own in a COX
    cell's window) per launch, averaged over chips, in ms."""
    if run.trace is None or not run.counters.get("launches"):
        return None
    return 1e3 * sum(run.trace.module_s.values()) / run.counters["launches"]


def cox_roofline(run):
    """Share of the roofline the COX launches reach: the least time the
    chip could take for each launch's algorithmic work (``bench/work.py``,
    from shapes), summed, over the device time of the COX programs in the
    traced window.  Over several chips the work is split between them, and
    the device time is the mean over chips."""
    if run.trace is None:
        return None
    device_s = sum(run.trace.module_s.values())
    if device_s <= 0:
        return None
    c = run.counters
    need = 0.0
    for s in run.in_window("cox.launch"):
        p = s.attrs["program"]
        flops, nbytes = work.cox_program(p, c["n"][p], c["blocks"][p])
        need += work.roofline_seconds(flops, nbytes, run.peaks)
    return 100.0 * need / run.trace.chips / device_s


def device_idle(run):
    """Share of the traced window in which no operation ran on the device,
    averaged over chips."""
    return None if run.trace is None else run.trace.idle_percent()
