"""reads_per_s: reads classified and demultiplexed into their requests
from the window's start to the end of the last cohort that finished in
the window, over that time (service counter, host clock).

Cohorts end in steps of a whole cohort (about 0.9 s in the long-read
cell), so a rate over the whole window would move in steps of a cohort's
share of it; this one resolves smaller changes.
"""


def read(run):
    if not run.finished:
        return None
    seconds, reads = run.finished[-1]
    return reads / seconds
