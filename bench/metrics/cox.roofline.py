"""``bench.readings.cox_roofline``, for the cells whose launches stand alone
(``cox_ms_per_launch``)."""

from bench.readings import cox_roofline as read  # noqa: F401
