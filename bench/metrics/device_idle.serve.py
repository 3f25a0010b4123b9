"""``bench.readings.device_idle``, for the served cells (``serve_tok_s``)."""

from bench.readings import device_idle as read  # noqa: F401
