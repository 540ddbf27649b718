"""Process start to the first timed submission: weights made and
staged, deploy, the SharedCache filled with the weights, and the
warm-up invocation that compiles."""


def read(run):
    return run["setup_s"]
