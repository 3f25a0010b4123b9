"""Model FLOPs of every token the window computed (2 per active weight
plus attention over the real positions, ``bench/work.py``; prefill tokens
included) per second of window, over the chip's peak."""


def read(run):
    flops = run.counters.get("model_flops")
    if not flops or run.peaks is None:
        return None
    return 100.0 * flops / run.window_s / run.peaks["peak_flops"]
