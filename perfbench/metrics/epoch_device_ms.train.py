"""Device busy time per training epoch completed in the traced window
(the epoch scan's kernels and each job's state initialisation)."""


def read(x):
    if x["epochs"] == 0:
        return None
    return 1e3 * x["trace"].busy_s / x["epochs"]
