"""A cell, configuration, traffic mix and per-layer metric added as new
files and entries are found by name, with no file that was there edited;
and the harness refuses to run without its program."""
import hashlib
import json
import pathlib

import pytest

from perfbench import harness
from perfbench.tests import minibench


def digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "perfbench").rglob("*")) if p.is_file()}


def test_an_added_cell_is_found_by_name(tmp_path):
    root = minibench.make_root(tmp_path, cells=["fsq.train"])
    before = digest(root)
    base = root / "perfbench"
    cfg = minibench.tiny_config("foursquare-t1")
    cfg["name"] = "tiny-extra"
    cfg["data"]["n_users"] = 90
    (base / "configs" / "tiny-extra.json").write_text(json.dumps(cfg))
    (base / "traffic" / "train-2ep.json").write_text(
        json.dumps({"kind": "train", "epochs_per_job": 2}))
    (base / "limits" / "extra.train.json").write_text(
        (base / "limits" / "fsq.train.json").read_text())
    (base / "metrics" / "jobs_run.train.py").write_text(
        "def read(x):\n    return float(x['jobs']) if x['jobs'] else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "extra.train", "config": "tiny-extra",
                               "traffic": "train-2ep", "chips": 1, "why": "t"})
    bench["end_to_end"][0]["workloads"].append("extra.train")
    bench["per_layer"].append({"name": "jobs_run.train", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "train_events_per_s",
                               "workloads": ["extra.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = digest(root)
    assert all(after[k] == v for k, v in before.items())   # nothing edited

    cell = harness.find_cell(root, "extra.train")
    assert cell.config["data"]["n_users"] == 90
    assert cell.traffic["epochs_per_job"] == 2
    assert [m["name"] for m in cell.per_layer] == ["jobs_run.train"]
    assert [m["name"] for m in cell.end_to_end] == ["train_events_per_s",
                                                    "setup_s"]
    read = harness.load_reader(cell.metrics_dir, "jobs_run.train")
    assert read({"jobs": 3}) == 3.0 and read({"jobs": 0}) is None
    # the existing cell does not pick up the new metric
    assert "jobs_run.train" not in [
        m["name"] for m in harness.find_cell(root, "fsq.train").per_layer]

    rc, res, err = minibench.run_cell(root, "extra.train", seconds=0.5)
    assert rc == 0, err
    assert res["correct"] is True, err
    assert set(res["metrics"]) == {"train_events_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_an_unknown_cell_or_a_missing_program_exits_without_a_result(tmp_path):
    root = minibench.make_root(tmp_path, cells=["fsq.train"])
    rc, res, err = minibench.run_cell(root, "no.such-cell")
    assert rc != 0 and res is None
    (root / "src").unlink()
    rc, res, err = minibench.run_cell(root, "fsq.train")
    assert rc != 0 and res is None and "program" in err


def test_a_cpu_backend_is_refused():
    with pytest.raises(harness.SetupError, match="no TPU"):
        harness.device_info(1, require_tpu=True)
