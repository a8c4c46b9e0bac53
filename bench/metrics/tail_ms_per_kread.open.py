"""Device ms of the jitted classifier tail (from_agreement) per 1000
live reads."""

from bench.readers import tail_ms_per_kread as read  # noqa: F401
