"""Least time the chip needs for the live reads' work (bench/roofline.py)
over the fused kernel's device time, in percent."""

from bench.readers import fused_roofline_pct as read  # noqa: F401
