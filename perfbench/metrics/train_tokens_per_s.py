"""All tokens of all steps completed in the window over the whole window:
first dispatch enqueued to last loss fetched on the host.  Host clock."""


def read(run):
    return run["tokens"] / run["window_s"]
