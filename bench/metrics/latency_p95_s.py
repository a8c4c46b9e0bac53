"""latency_p95_s: 95th percentile of request latency, due time to final
report, over every request due in the window (exact order statistic).
"""

from bench.harness import nearest_rank


def read(run):
    return nearest_rank(run.latencies_s, 95) if run.latencies_s else None
