"""Device ms of the fused encode+search kernel per 1000 live reads."""

from bench.readers import fused_ms_per_kread as read  # noqa: F401
