"""Process start to the first timed request: imports, weights, plan,
warm-up, and on a cold first run the compiles."""


def value(run):
    return run.setup_s
