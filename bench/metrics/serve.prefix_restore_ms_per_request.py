"""Device time of putting the shared context back before each request,
in ms a request: the module time of ``jit_prefix_restore`` (the jitted
``launch/serve.py`` ``prefix_restore``, which copies a snapshot's ring
rows into the slot) in the traced window, over the requests the window
served.  None where no such module ran."""


def read(run):
    if run.trace is None or not run.counters.get("requests"):
        return None
    restore_s = run.trace.module_s.get("jit_prefix_restore", 0.0)
    if restore_s <= 0:
        return None
    return 1e3 * restore_s / run.counters["requests"]
