"""Host ms per cohort in ``serve.assemble``: padding the cohort's rows
into the ``(batch, bucket)`` arrays (the program's spans)."""

from bench.program_spans import assemble_ms_per_cohort as read  # noqa: F401
