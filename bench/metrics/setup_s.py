"""setup_s: process start to the first due request (host clock).

Loading, data generation, loading the RefDB from the store, cohort-shape
warm-up and, in a run that compiles, compilation.  A seed's first run
builds the RefDB before loading it; that build is timed apart and left
out, so every run's set-up does the same work.
"""


def read(run):
    return run.setup_s
