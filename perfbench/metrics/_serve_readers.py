"""The serving cells' per-layer readings, shared by the readers of the
cells below the knee (`<name>.serve.py`, moving `serve_p95_ms`) and above
it (`<name>.over.py`, moving `serve_rps`). Each returns None where the
window holds nothing to read."""
import numpy as np

from perfbench.metrics import _serve_work as _work


def batch_fill(x):
    """Real requests over the slots the window's dispatches carried
    (dispatches times the microbatch), in %."""
    d = x["dispatches"]
    if len(d) == 0:
        return None
    return 100.0 * float(d[:, 2].sum()) / (len(d) * x["microbatch"])


def device_idle(x):
    """Share of the traced window in which no operation ran on the device:
    1 - busy / window, busy the union of the device's op intervals, in %."""
    t = x["trace"]
    if t.window_s <= 0 or len(x["dispatches"]) == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def dispatch_ms(x):
    """Median wall time of one `ServingEngine.serve_microbatch` call, timed
    by the benchmark's own span around it (the call ends in
    block_until_ready and the copies of the slates to the host)."""
    d = x["dispatches"]
    if len(d) == 0:
        return None
    return 1e3 * float(np.median(d[:, 1] - d[:, 0]))


def mfu(x):
    """The whole serve step's share of the chip's peak: the operations of a
    dispatch (2 c K per request, the mean over the window's dispatches)
    over the median dispatch wall time times the peak bf16 FLOP/s, in %."""
    d = x["dispatches"]
    flops, _ = _work.work(x)
    if len(d) == 0 or flops == 0:
        return None
    wall = float(np.median(d[:, 1] - d[:, 0]))
    return 100.0 * (flops / len(d)) / (wall * x["peak"]["bf16_flops_per_s"])


def _p95_ms(wait):
    if len(wait) == 0:
        return None
    p = float(np.percentile(np.where(np.isnan(wait), np.inf, wait), 95))
    return 1e3 * p if np.isfinite(p) else None


def queue_p95_ms(x):
    """95th percentile of the time a request waits in the client's queue:
    from when it was due until the dispatch that serves it starts."""
    return _p95_ms(x["start"] - x["times"])


def latency_p95_ms(x):
    """95th percentile of the latency of the requests due in the window:
    from when each was due until its slate is back on the host."""
    return _p95_ms(x["done"] - x["times"])


def topk_roofline(x):
    """Least time of the window's served requests on this chip (candidate
    gather and window top-k: the larger of operations over peak FLOP/s and
    bytes over peak HBM bandwidth; the bytes bound it) over the device busy
    time of the traced window, in %."""
    busy = x["trace"].busy_s
    flops, nbytes = _work.work(x)
    if busy <= 0 or nbytes == 0:
        return None
    pk = x["peak"]
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
