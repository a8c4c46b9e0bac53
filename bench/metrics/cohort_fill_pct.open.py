"""Live rows over cohorts run times the batch size, across the window
(the service's public counters)."""

from bench.readers import cohort_fill_pct as read  # noqa: F401
