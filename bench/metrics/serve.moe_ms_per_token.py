"""Device time of the decode MoE kernel per token pass, in ms: the self
time of the ops named ``moe_decode_experts`` (the Pallas call's name
reaches the op text) over the window's token passes, each decode step
and each prompt token, since prefill runs the same decode step per
prompt token.  None where the step has no such kernel."""


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.ops_matching(r"\bmoe_decode_experts\b")
    if kernel_s <= 0:
        return None
    passes = len(run.counters["steps"])
    passes += sum(tokens for _, tokens in run.counters["prefills"])
    return 1e3 * kernel_s / passes
