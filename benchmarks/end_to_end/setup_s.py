"""Process start to the first timed request or step: loading, weights, warm-up and, in a cold run, compilation."""

def read(records):
    return records["setup_s"], "s"
