"""Share of the roofline the Mamba2 decode state update reaches: the
least time the chip could take for every ``ssd_decode`` call of the
traced run (``bench/work_hybrid.py``: the f32 state read and written once
and the call's operands, from shapes; bound by HBM bandwidth), over the
device time of the ops named ``ssd_decode``.  Each token pass, decode
step or prompt token, calls it once a Mamba2 layer."""

from bench import work, work_hybrid

SSD_DECODE = r"^%?ssd_decode(\.\d+)? = "


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = run.trace.ops_matching(SSD_DECODE)
    if kernel_s <= 0:
        return None
    passes = len(run.counters["steps"])
    passes += sum(tokens for _, tokens in run.counters["prefills"])
    calls = passes * work_hybrid.mamba_layers(run.cfg)
    per_call = work.roofline_seconds(*work_hybrid.ssd_decode_work(run.cfg), run.peaks)
    return 100.0 * calls * per_call / kernel_s
