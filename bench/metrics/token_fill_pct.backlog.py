"""Live tokens over slots x bucket across the window's cohorts, from the
``serve.step`` spans' arguments."""

from bench.program_spans import token_fill as read  # noqa: F401
