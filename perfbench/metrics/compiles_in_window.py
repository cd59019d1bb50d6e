"""Backend compilations that jax.monitoring reported inside the window.
Must read 0: the run is not correct otherwise."""


def read(run):
    return run["compiles_in_window"]
