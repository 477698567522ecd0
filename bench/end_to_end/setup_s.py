"""Process start to the first timed call: imports, weights, the plan
daemon's first period, compiles (or cache loads) and warm-up."""


def read(run):
    return run.setup_s
