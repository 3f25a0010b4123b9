"""``bench.readings.cox_roofline``, for the cells that chain dependent
launches between syncs (``cox_ms_per_launch.chain``)."""

from bench.readings import cox_roofline as read  # noqa: F401
