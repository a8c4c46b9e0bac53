"""Chip benchmark of the Demeter profiler's served path (see run.py)."""
