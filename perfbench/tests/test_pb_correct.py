"""`correct` comes out false when the timed path is broken underneath a
whole run (the harness's look for a chip skipped, at a test's size), and
the control, the reference in bfloat16 in the program's place, fails the
cells' limits; the program itself passes them."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import checks, data
from perfbench.kinds import serve, train
from perfbench.refs import dmf as ref
from perfbench.tests import minibench

TRAIN_CELLS = ["fsq.train", "alipay.train-dp"]
SERVE_CELLS = ["fsq.serve-over", "fsq.serve-poisson", "alipay.serve-onoff"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return minibench.make_root(tmp_path_factory.mktemp("bench"),
                               cells=TRAIN_CELLS + SERVE_CELLS)


@pytest.fixture
def busy_root(tmp_path):
    """Serving at a rate that fills the microbatches."""
    root = minibench.make_root(tmp_path, cells=SERVE_CELLS)
    for f in (root / "perfbench" / "traffic").glob("*.json"):
        trf = json.loads(f.read_text())
        trf["arrivals"] = dict(trf["arrivals"], rate_rps=4000.0)
        f.write_text(json.dumps(trf))
    return root


@pytest.mark.parametrize("cell", TRAIN_CELLS + SERVE_CELLS)
def test_the_program_is_correct(root, cell):
    rc, res, err = minibench.run_cell(root, cell, seconds=0.5)
    assert rc == 0 and res["correct"] is True, err
    assert res["failed"] == 0 and res["window_compiles"] == 0


def _epoch_fault(monkeypatch, fault):
    from repro.core import dmf
    orig = dmf._epoch_scan

    def broken(U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, dp_seed, cfg,
               tele=False):
        if fault == "unchanged":
            out = orig(jnp.copy(U), jnp.copy(P), jnp.copy(Q), nbr_idx, nbr_wgt,
                       ui, vj, r, conf, dp_seed, cfg, tele=tele)
            return (U, P, Q) + tuple(out[3:])
        if fault == "no_exchange":      # each message reaches its sender only
            own = nbr_idx == jnp.arange(nbr_idx.shape[0])[:, None]
            return orig(U, P, Q, nbr_idx, jnp.where(own, nbr_wgt, 0.0), ui, vj,
                        r, conf, dp_seed, cfg, tele=tele)
        half = conf.shape[1] // 2
        conf = jnp.concatenate([2.0 * conf[:, :half],
                                jnp.zeros_like(conf[:, half:])], axis=1)
        return orig(U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, dp_seed, cfg,
                    tele=tele)

    monkeypatch.setattr(dmf, "_epoch_scan", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_broken_training_step_is_caught(root, cell, fault, monkeypatch):
    _epoch_fault(monkeypatch, fault)
    rc, res, err = minibench.run_cell(root, cell, seconds=0.3)
    assert rc == 0 and res["correct"] is False, err


def _dispatch_fault(monkeypatch, fault):
    from repro.serving import engine
    orig = engine._dispatch_rows

    def broken(U, P, Q, seen, bucket_items, user_bucket, uids, *, k, prune):
        if fault == "half_batch":
            half = uids.shape[0] // 2
            uids = jnp.concatenate([uids[:half], uids[:half]])
        vals, idx = orig(U, P, Q, seen, bucket_items, user_bucket, uids,
                         k=k, prune=prune)
        if fault == "altered":
            idx = idx.at[:, 0].set(idx[:, 1])
        if fault == "tie_order":        # ids tied at 0 served highest first
            zero = vals == 0.0
            first = jnp.argmax(zero, axis=1)[:, None]
            last = first + zero.sum(axis=1, keepdims=True) - 1
            slot = jnp.arange(idx.shape[1])[None, :]
            idx = jnp.take_along_axis(
                idx, jnp.where(zero, first + last - slot, slot), axis=1)
        return vals, idx

    monkeypatch.setattr(engine, "_dispatch_rows", broken)


@pytest.mark.parametrize("fault", ["half_batch", "altered", "tie_order"])
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_a_broken_serving_dispatch_is_caught(busy_root, cell, fault,
                                             monkeypatch):
    _dispatch_fault(monkeypatch, fault)
    rc, res, err = minibench.run_cell(busy_root, cell, seconds=0.3)
    assert rc == 0 and res["correct"] is False, err


def _cell(root, name):
    from perfbench import harness
    return harness.find_cell(root, name)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_training_control_fails_the_limits(root, cell):
    c = _cell(root, cell)
    ds = data.from_config(c.config)
    M = ref.walk_for(c.config, ds)
    js = train.job_seed(5, 0)
    r32 = ref.Trainer(c.config, c.traffic, ds, M).run(js, train.COMPARED_EPOCHS)
    r16 = ref.Trainer(c.config, c.traffic, ds, M, dtype=jnp.bfloat16).run(
        js, train.COMPARED_EPOCHS)
    dists = np.asarray(ref.leaf_dists(*r32["state"], *r16["state"]))
    ok, table = checks.judge(checks.train_numbers(r16, r32, dists), c.limits)
    assert not ok, table


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_the_serving_control_fails_the_limits(root, cell):
    c = _cell(root, cell)
    ds = data.from_config(c.config)
    users = np.random.default_rng(3).integers(0, ds.n_users, 200)
    shape = (ds.n_users, ds.n_items, c.config["model"]["dim"],
             float(c.traffic["factor_scale"]), float(c.traffic["live_share"]))
    numbers = serve.compare(ds, shape, 5, users, None, None, c.traffic["k"],
                            dtype="bfloat16")
    ok, table = checks.judge(numbers, c.limits)
    assert not ok, table
