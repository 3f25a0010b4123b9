"""Share of the window the serving loop spends in ``prefill_prompt``
(harness span ``serve.prefill``)."""


def read(run):
    lo, hi = run.window
    inside = [
        min(s.end_ns, hi) - max(s.start_ns, lo)
        for s in run.spans.named("serve.prefill")
        if s.end_ns > lo and s.start_ns < hi
    ]
    return 100.0 * sum(inside) / (hi - lo) if inside else None
