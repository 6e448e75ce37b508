"""Process start to the first due request: imports, deploy (weights, compiles), warm-up."""


def read(run):
    return run["setup_s"]
