"""Host ms per cohort in ``serve.demux``: rows split into the requests'
reports, finished requests finalized (the program's spans)."""

from bench.program_spans import demux_ms_per_cohort as read  # noqa: F401
