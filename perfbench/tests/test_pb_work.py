"""Operation and byte counts of the per-layer metrics against hand-worked
shapes, the readers on given inputs, and the peak table."""
import importlib.util
import pathlib

import numpy as np
import pytest

from perfbench import peaks
from perfbench.xtrace import Reduction

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def train_inputs(dp=False, busy=0.5, window=1.0, epochs=4):
    return {"dim": 10, "events_per_epoch": 256, "receivers_per_epoch": 512.0,
            "dp": dp, "epochs": epochs, "window_s": window, "peak": PEAK,
            "trace": Reduction(window_s=window, busy_s=busy, device_ops=[],
                               idle_gaps=[], n_device_events=1)}


def test_train_epoch_work_by_hand():
    work = load("_train_work")
    # per event 16K+5 = 165 ops, 16+20K = 216 B; per receiver 2K = 20 ops,
    # 8K+8 = 88 B
    assert work.epoch_work(train_inputs()) == (256 * 165 + 512 * 20,
                                              256 * 216 + 512 * 88)
    # DP: clip and noise add 4K ops, the noise row's write and read 8K B
    assert work.epoch_work(train_inputs(dp=True)) == (
        256 * 205 + 512 * 20, 256 * 296 + 512 * 88)


def test_train_readers():
    x = train_inputs()
    flops, nbytes = 52480.0, 100352.0
    assert load("device_idle.train").read(x) == pytest.approx(50.0)
    assert load("epoch_device_ms.train").read(x) == pytest.approx(125.0)
    least = max(flops / 197e12, nbytes / 819e9)
    assert load("epoch_roofline.train").read(x) == pytest.approx(
        100 * least / 0.125)
    assert load("mfu.train").read(x) == pytest.approx(
        100 * flops * 4 / 1.0 / 197e12)
    none = train_inputs(epochs=0)
    for m in ("device_idle.train", "epoch_device_ms.train",
              "epoch_roofline.train", "mfu.train"):
        assert load(m).read(none) is None


def serve_inputs():
    # three requests: 3 and 5 candidates, one popularity slate; one
    # never served; two dispatches of a microbatch of 4
    return {"dim": 10, "microbatch": 4, "peak": PEAK,
            "candidates": np.array([3, 0, 5, 7]),
            "times": np.array([0.0, 0.0, 0.001, 0.002]),
            "start": np.array([0.0, 0.0, 0.003, np.nan]),
            "done": np.array([0.002, 0.002, 0.005, np.nan]),
            "dispatches": np.array([[0.0, 0.002, 2], [0.003, 0.005, 1]]),
            "trace": Reduction(window_s=1.0, busy_s=1e-4, device_ops=[],
                               idle_gaps=[], n_device_events=2)}


def test_serve_work_by_hand():
    work = load("_serve_work")
    # 2cK ops; u rows 4K B each, 4cK B of v rows, c/8 B of seen bits
    assert work.work(serve_inputs()) == (160.0, 3 * 40 + 4 * 10 * 8 + 1.0)


def test_serve_readers():
    x = serve_inputs()
    assert load("batch_fill.serve").read(x) == pytest.approx(100 * 3 / 8)
    assert load("dispatch_ms.serve").read(x) == pytest.approx(2.0)
    assert load("device_idle.serve").read(x) == pytest.approx(99.99)
    assert load("topk_roofline.serve").read(x) == pytest.approx(
        100 * max(160 / 197e12, 441 / 819e9) / 1e-4)
    assert load("mfu.serve").read(x) == pytest.approx(
        100 * (160 / 2) / (0.002 * 197e12))
    # queue waits 0, 0, 2 ms and one never served: the p95 is missing
    assert load("queue_p95_ms.serve").read(x) is None
    x["done"][3], x["start"][3] = 0.004, 0.003
    assert load("queue_p95_ms.serve").read(x) == pytest.approx(
        1e3 * np.percentile([0, 0, 0.002, 0.001], 95))


@pytest.mark.parametrize("name", ["batch_fill", "dispatch_ms", "device_idle",
                                  "topk_roofline", "mfu"])
def test_above_knee_readers_read_as_their_below_knee_twins(name):
    x = serve_inputs()
    assert load(f"{name}.over").read(x) == load(f"{name}.serve").read(x)


def test_latency_p95_over_all_requests_due():
    x = serve_inputs()
    # latencies 2, 2, 4 ms and one never served: the p95 is missing
    assert load("serve_p95_ms.over").read(x) is None
    x["done"][3] = 0.004
    assert load("serve_p95_ms.over").read(x) == pytest.approx(
        1e3 * np.percentile([0.002, 0.002, 0.004, 0.002], 95))


def test_peak_table_knows_the_v5e_and_refuses_others():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")
