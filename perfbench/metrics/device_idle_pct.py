"""1 - union of device-op intervals over the traced window, of the device
with the largest idle share."""


def read(run):
    if run["trace"] is None:
        return None
    return 100.0 * run["trace"]["idle_share_max"]
