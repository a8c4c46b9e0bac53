"""Share of the traced window in which no operation ran on the chip."""

from bench.readers import device_idle_pct as read  # noqa: F401
