"""Device time of decode attention on the sliding layers' K/V rings per
token pass, in ms: the self time of the ops whose own name is
``decode_attn_ring`` (the Pallas call's name is the op's) over the
window's token passes, each decode step and each prompt token, since
prefill runs the same decode step per prompt token.  None where the step
has no such kernel."""

DECODE_ATTN_RING = r"^%?decode_attn_ring(\.\d+)? = "


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.ops_matching(DECODE_ATTN_RING)
    if kernel_s <= 0:
        return None
    passes = len(run.counters["steps"])
    passes += sum(tokens for _, tokens in run.counters["prefills"])
    return 1e3 * kernel_s / passes
