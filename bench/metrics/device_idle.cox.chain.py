"""``bench.readings.device_idle``, for the COX cells that chain dependent
launches between syncs (``cox_ms_per_launch.chain``)."""

from bench.readings import device_idle as read  # noqa: F401
