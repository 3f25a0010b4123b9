"""Host time of the COX token pipeline's ``step`` (the captured graph's
replay) per decode step, harness span ``serve.pipeline``, in ms."""


def read(run):
    return run.mean_span_ms("serve.pipeline")
