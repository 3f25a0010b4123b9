"""``bench.readings.device_ms_per_launch``, for the cells whose launches stand
alone (``cox_ms_per_launch``)."""

from bench.readings import device_ms_per_launch as read  # noqa: F401
