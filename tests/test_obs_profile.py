"""Spans and scopes on the profiler's clock (src/repro/obs/trace.py).

A CPU profile of one served microbatch and of one training job holds the
program's host spans, nested as their names promise; the lowered epoch
and serve programs carry their `jax.named_scope` names; and with no
profiler session and the Chrome tracer off a span is the shared null
context and records nothing. (That the scopes leave the compiled v5e
programs unchanged is checked in tests/test_tpu_compile.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import dmf, graph
from repro.data import synthetic_poi
from repro.obs import trace as trace_lib
from repro.serving import ServingConfig, ServingEngine, index_from_dataset
from repro.serving import engine as engine_lib

SERVE_CHILDREN = ("serve.prepare", "serve.launch", "serve.device_wait",
                  "serve.fetch", "serve.fallback")
EPOCH_CHILDREN = ("fit.sample", "fit.h2d", "fit.launch", "fit.loss_sync")


@pytest.fixture(scope="module")
def world():
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=80, n_items=50, n_ratings=600, n_cities=4, seed=0))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        beta=0.1, gamma=0.01, batch_size=64)
    return ds, nbr, cfg


def _host_events(tmp_path, fn):
    """(name, start ns, end ns, stats) of every host event that a profiler
    session records around ``fn()``, Python tracer off."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]


def _nested(events, parent, children):
    """The ``parent`` events, each asserted to hold every one of
    ``children`` exactly once; returns [(parent event, summed children)]."""
    out = []
    for p in (e for e in events if e[0] == parent):
        total = 0
        for c in children:
            inside = [e for e in events
                      if e[0] == c and e[1] >= p[1] and e[2] <= p[2]]
            assert len(inside) == 1, (parent, c, inside)
            total += inside[0][2] - inside[0][1]
        out.append((p, total))
    return out


def test_serve_microbatch_spans_nest_under_the_call(world, tmp_path):
    ds, nbr, cfg = world
    state = dmf.fit(cfg, ds.train, nbr, epochs=1).state
    eng = ServingEngine(state, index_from_dataset(ds),
                        ServingConfig(microbatch=8, k=5), train=ds.train)
    ids = np.array([0, 3, 7, -1, ds.n_users + 5])  # two take the fallback
    want = eng.serve_microbatch(ids)               # compiles, untraced
    n_chrome = len(trace_lib.get_tracer().events())
    got = []
    events = _host_events(tmp_path,
                          lambda: got.append(eng.serve_microbatch(ids)))
    for w, g in zip(want[:2], got[0][:2]):
        np.testing.assert_array_equal(w, g)
    (call, children), = _nested(events, "engine.serve_microbatch",
                                SERVE_CHILDREN)
    assert call[3]["n_real"] == len(ids)
    assert children <= call[2] - call[1]
    # the Chrome tracer is off: the session saw the spans, it did not
    assert len(trace_lib.get_tracer().events()) == n_chrome


def test_fit_epoch_spans_nest_under_the_epoch(world, tmp_path):
    ds, nbr, cfg = world
    events = _host_events(tmp_path,
                          lambda: dmf.fit(cfg, ds.train, nbr, epochs=2))
    epochs = _nested(events, "fit.epoch", EPOCH_CHILDREN)
    assert sorted(p[3]["epoch"] for p, _ in epochs) == [0, 1]
    (init,) = [e for e in events if e[0] == "fit.init"]
    assert init[2] <= min(p[1] for p, _ in epochs)
    for p, children in epochs:
        assert children <= p[2] - p[1]


def test_lowered_programs_carry_the_scopes(world):
    ds, _, cfg = world
    I, J, K, S, nb, B = ds.n_users, ds.n_items, cfg.dim, 5, 2, cfg.batch_size

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    epoch = dmf._epoch_scan.lower(
        sds((I, K)), sds((I, J, K)), sds((I, J, K)), sds((I, S), jnp.int32),
        sds((I, S)), sds((nb, B), jnp.int32), sds((nb, B), jnp.int32),
        sds((nb, B)), sds((nb, B)), sds((), jnp.int32), cfg,
    ).as_text(debug_info=True)
    for scope in ("dmf.gather_grads", "dmf.local_update", "dmf.p_scatter"):
        assert scope in epoch, scope
    serve = engine_lib._dispatch_rows.lower(
        sds((I, K)), sds((I, J, K)), sds((I, J, K)), sds((I, J), jnp.int8),
        sds((4, 32), jnp.int32), sds((I,), jnp.int32), sds((8,), jnp.int32),
        k=5, prune=True,
    ).as_text(debug_info=True)
    for scope in ("serve.window_gather", "serve.topk"):
        assert scope in serve, scope


def test_span_off_the_profiler_is_the_null_context_and_records_nothing():
    tracer = trace_lib.get_tracer()
    assert not tracer.enabled
    assert not trace_lib._profiling()
    n = len(tracer.events())
    assert trace_lib.span("fit.epoch", epoch=0) is trace_lib._NULL
    with trace_lib.span("serve.fetch") as sp:
        assert sp is None
    assert len(tracer.events()) == n
