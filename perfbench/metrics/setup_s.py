"""Process start to the first measured dispatch: imports, weights, model
build, compile or cache hit, the first two dispatches.  Host clock."""


def read(run):
    return run["setup_s"]
