"""Least time of one epoch's work on this chip (the larger of operations
over peak FLOP/s and bytes over peak HBM bandwidth; the bytes bound it)
over the device busy time per epoch."""
from perfbench.metrics import _train_work as _work


def read(x):
    if x["epochs"] == 0 or x["trace"].busy_s <= 0:
        return None
    flops, nbytes = _work.epoch_work(x)
    pk = x["peak"]
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (x["trace"].busy_s / x["epochs"])
