"""latency_p50_s: median request latency, due time to final report.

An exact order statistic over every request due in the window.
"""

from bench.harness import nearest_rank


def read(run):
    return nearest_rank(run.latencies_s, 50) if run.latencies_s else None
