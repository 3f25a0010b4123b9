"""``bench.readings.device_idle``, for the COX cells whose launches stand
alone (``cox_ms_per_launch``)."""

from bench.readings import device_idle as read  # noqa: F401
