"""``bench.readings.device_ms_per_launch``, for the cells that chain
dependent launches between syncs (``cox_ms_per_launch.chain``)."""

from bench.readings import device_ms_per_launch as read  # noqa: F401
