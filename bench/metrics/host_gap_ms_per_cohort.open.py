"""Device-idle ms per cohort inside the service steps that ran cohorts:
admission, assembly and dispatch before the call, results to the host and
demux after it."""

from bench.readers import host_gap_ms_per_cohort as read  # noqa: F401
