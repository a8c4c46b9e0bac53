"""Host ms per cohort in ``session.dispatch``: copies to the device, the
backend call and the tail launched, no result waited for (the program's
spans)."""

from bench.program_spans import dispatch_ms_per_cohort as read  # noqa: F401
