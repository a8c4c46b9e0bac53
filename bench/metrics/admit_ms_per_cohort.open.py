"""Host ms per cohort in ``serve.admit``: activating requests, pulling
their reads and forming the cohort (the program's spans)."""

from bench.program_spans import admit_ms_per_cohort as read  # noqa: F401
