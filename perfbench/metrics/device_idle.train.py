"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window, busy the union of the device's op intervals."""


def read(x):
    t = x["trace"]
    if t.window_s <= 0 or x["epochs"] == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
