"""Process start to the first due time: weights, compression, warm-up."""


def read(run):
    return run.setup_s
