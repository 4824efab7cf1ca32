"""The whole training step's share of the chip's peak: operations per
rated event (`_train_work.py`) times the traced window's events per
second, over the peak bf16 FLOP/s."""
from perfbench.metrics import _train_work as _work


def read(x):
    if x["epochs"] == 0:
        return None
    flops, _ = _work.epoch_work(x)
    per_s = flops * x["epochs"] / x["window_s"]
    return 100.0 * per_s / x["peak"]["bf16_flops_per_s"]
