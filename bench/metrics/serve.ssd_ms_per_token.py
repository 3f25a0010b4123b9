"""Device time of the Mamba2 decode state update per token pass, in ms:
the self time of the ops named ``ssd_decode`` (the Pallas call's name is
the op's own) over the window's token passes, each decode step and each
prompt token, since prefill runs the same decode step per prompt token.
None where the step has no such kernel."""

SSD_DECODE = r"^%?ssd_decode(\.\d+)? = "


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.ops_matching(SSD_DECODE)
    if kernel_s <= 0:
        return None
    passes = len(run.counters["steps"])
    passes += sum(tokens for _, tokens in run.counters["prefills"])
    return 1e3 * kernel_s / passes
