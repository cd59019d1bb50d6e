"""Window seconds over steps completed, in the traced run."""


def read(run):
    return 1e3 * run["window_s"] / run["steps"]
