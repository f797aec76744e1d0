"""repro.obs: metrics semantics, span reconstruction, serve integration.

The acceptance test at the bottom runs the streaming soak from ISSUE —
register → appends → queries → flush under tracing — and reconstructs
every request's bucket / cache hit-miss / staleness / prune-occupancy
chain purely from the buffered span events.
"""

import json
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram, log_bucket_bounds
from repro.serve import QueryRequest, ServeConfig, ServeEngine
from repro.serve.stats import LatencyRecorder


def _q(eng, key, y, **kw):
    """One typed query, densities out."""
    return eng.query(QueryRequest(key=key, points=y, **kw)).value

D, H = 4, 0.5


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test sees default flags and leaves no trace events behind."""
    m0, t0 = obs.state.metrics_on, obs.state.trace_on
    obs.configure(metrics=True, trace=False)
    yield
    obs.configure(metrics=m0, trace=t0)
    obs.clear_trace()


@pytest.fixture(scope="module")
def data():
    kx, ka, ky = jax.random.split(jax.random.PRNGKey(0), 3)
    return (np.asarray(jax.random.normal(kx, (256, D)), np.float32),
            np.asarray(jax.random.normal(ka, (32, D)), np.float32),
            np.asarray(jax.random.normal(ky, (64, D)), np.float32))


# ---------------------------------------------------------------------------
# Histogram core: bucket boundaries, quantile edge cases, bounded state.
# ---------------------------------------------------------------------------


def test_log_bucket_bounds_spacing():
    b = log_bucket_bounds(1e-3, 1.0, per_decade=6)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1.0
    for lo, hi in zip(b, b[1:]):
        assert hi / lo == pytest.approx(10 ** (1 / 6))


def test_histogram_boundary_value_lands_in_its_edge_bucket():
    h = Histogram("t.edges", lo=1e-3, hi=1.0, per_decade=6)
    edge = h.bounds[3]
    h.observe(edge)                       # exactly ON an upper edge
    assert h.counts[3] == 1               # bisect_left: le-inclusive
    h.observe(edge * 1.0001)              # just past it
    assert h.counts[4] == 1
    h.observe(1e-9)                       # below lo -> first bucket
    assert h.counts[0] == 1
    h.observe(1e9, k=5)                   # past hi -> overflow, weighted
    assert h.counts[-1] == 5 and h.count == 8


def test_histogram_quantile_empty_and_single():
    h = Histogram("t.q", lo=1e-3, hi=1.0)
    assert h.quantile(0.5) == 0.0 and h.quantile(0.99) == 0.0
    h.observe(0.0123)
    for q in (0.01, 0.5, 0.99):           # 1 sample: exact at every q
        assert h.quantile(q) == pytest.approx(0.0123)


def test_histogram_quantile_resolution_bar():
    h = Histogram("t.res", lo=1e-5, hi=1e3, per_decade=6)
    samples = [0.001, 0.002, 0.004, 1.5]
    for s in samples:
        h.observe(s)
    edge_ratio = 10 ** (1 / 6)
    p50, exact = h.quantile(0.5), 0.002
    assert exact / edge_ratio <= p50 <= exact * edge_ratio
    # min/max clamping is exact regardless of bucket resolution
    assert h.quantile(0.999) <= 1.5 and h.quantile(0.001) >= 0.001


def test_histogram_state_is_bounded():
    h = Histogram("t.bounded", lo=1e-5, hi=1e3)
    n_buckets = len(h.counts)
    for i in range(10_000):
        h.observe(1e-4 * (1 + i % 997))
    assert len(h.counts) == n_buckets and h.count == 10_000


def test_counter_and_disabled_fast_path():
    c = obs.counter("t.obs.ctr")
    c.reset()
    c.inc(); c.inc(2.0)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)
    obs.configure(metrics=False)
    c.inc(100)
    obs.histogram("t.obs.h").observe(1.0)
    obs.gauge("t.obs.g").set(7)
    assert c.value == 3.0
    assert obs.histogram("t.obs.h").count == 0
    assert obs.gauge("t.obs.g").value == 0.0


# ---------------------------------------------------------------------------
# LatencyRecorder (satellite 1): bounded, JSON-safe, exact small-n.
# ---------------------------------------------------------------------------


def test_latency_recorder_empty_summary_json_safe():
    s = LatencyRecorder().summary()
    assert s.count == 0 and s.queries == 0
    assert s.qps == 0.0 and s.p50_ms == 0.0 and s.p99_ms == 0.0
    # allow_nan=False raises on any bare NaN/Inf — the downstream contract
    doc = json.dumps(s.as_dict(), allow_nan=False)
    assert "NaN" not in doc
    for v in s.as_dict().values():
        assert not (isinstance(v, float) and math.isnan(v))


def test_latency_recorder_single_sample_exact():
    r = LatencyRecorder()
    r.record(0.020, n_queries=64)
    s = r.summary()
    assert s.count == 1 and s.queries == 64
    assert s.p50_ms == pytest.approx(20.0)
    assert s.p99_ms == pytest.approx(20.0)
    assert s.qps == pytest.approx(64 / 0.020)


def test_latency_recorder_bounded_and_coalesce_weighting():
    r = LatencyRecorder()
    n_buckets = len(r._hist.counts)
    for _ in range(5000):
        r.record(0.001, n_queries=3, n_requests=4)
    assert len(r._hist.counts) == n_buckets
    s = r.summary()
    assert s.count == 20_000 and s.queries == 15_000
    r.reset()
    assert r.summary().count == 0


# ---------------------------------------------------------------------------
# Registry: snapshot stability across reset, prometheus exposition.
# ---------------------------------------------------------------------------


def test_snapshot_stable_across_reset():
    obs.counter("t.stab.c").inc(5)
    obs.gauge("t.stab.g").set(2.5)
    obs.histogram("t.stab.h", lo=1e-3, hi=1.0).observe(0.1, k=3)
    before = obs.metrics_snapshot()
    obs.registry.reset()
    after = obs.metrics_snapshot()
    assert set(after) == set(before)      # instrument set survives reset
    assert after["t.stab.c"]["value"] == 0.0
    assert after["t.stab.g"]["value"] == 0.0
    assert after["t.stab.h"]["count"] == 0
    assert before["t.stab.c"]["value"] == 5.0
    json.dumps(after, allow_nan=False)    # still JSON-safe when zeroed


def test_prometheus_exposition_lints_clean():
    obs.counter("t.prom.requests", "requests").inc()
    obs.histogram("t.prom.lat_s", lo=1e-4, hi=10.0).observe(0.02)
    obs.counter("t.prom.labeled", labels={"mode": "a b"}).inc()
    text = obs.prometheus_text()
    assert obs.lint_prometheus(text) == []
    assert "t_prom_lat_s_bucket" in text and 'le="+Inf"' in text


def test_prometheus_lint_catches_problems():
    bad = "\n".join([
        "# TYPE ok counter",
        "ok 1.0",
        "0bad_name 2.0",            # illegal leading digit
        "untyped_sample 3.0",       # no TYPE declared
        "# TYPE h histogram",
        'h_bucket{le="+Inf"} 1',    # histogram missing _sum/_count
        "ok not-a-number",
    ])
    problems = obs.lint_prometheus(bad)
    text = "\n".join(problems)
    assert "0bad_name" in text
    assert "untyped_sample" in text
    assert "missing series" in text
    assert "not-a-number" in text


# ---------------------------------------------------------------------------
# Spans: nesting/ordering under coalesced dispatch; engine metrics surface.
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering_under_query_many(data):
    x, _, y = data
    obs.configure(trace=True)
    obs.clear_trace()
    eng = ServeEngine(ServeConfig(backend="jnp", min_batch=16,
                                  max_batch=128))
    eng.register("t", x, h=H)
    eng.query_many([QueryRequest(key="t", points=q)
                    for q in (y[:5], y[:17], y[:3])])
    ev = obs.trace_events()
    req = [e for e in ev if e["name"] == "serve.request"]
    disp = [e for e in ev if e["name"] == "serve.dispatch"]
    buck = [e for e in ev if e["name"] == "serve.bucket"]
    assert len(req) == 1 and req[0]["attrs"]["requests"] == 3
    assert len(disp) == 1 and disp[0]["parent"] == req[0]["id"]
    assert len(buck) == 1 and buck[0]["parent"] == disp[0]["id"]
    assert buck[0]["attrs"]["rows"] == 25          # coalesced 5+17+3
    assert buck[0]["attrs"]["cache"] == "miss"
    # children close (and are buffered) before parents; timestamps nest
    order = [e["name"] for e in ev if e["name"].startswith("serve.")]
    assert order.index("serve.bucket") < order.index("serve.dispatch")
    assert order.index("serve.dispatch") < order.index("serve.request")
    assert req[0]["ts_us"] <= disp[0]["ts_us"] <= buck[0]["ts_us"]
    assert buck[0]["dur_us"] <= req[0]["dur_us"]
    # a second identical dispatch reuses the executable
    eng.query_many([QueryRequest(key="t", points=q)
                    for q in (y[:5], y[:17], y[:3])])
    last = obs.trace_events()[-3:]
    hit = [e for e in last if e["name"] == "serve.bucket"]
    assert hit and hit[0]["attrs"]["cache"] == "hit"
    # reconstruction helper: the tree groups children under parent ids
    tree = obs.span_tree(obs.trace_events())
    assert any(c["name"] == "serve.dispatch" for c in tree[req[0]["id"]])


def test_engine_metrics_surface(data):
    x, _, y = data
    eng = ServeEngine(ServeConfig(backend="jnp", min_batch=16,
                                  max_batch=128))
    eng.register("t", x, h=H)
    _q(eng, "t", y[:9])
    _q(eng, "t", y[:9])
    m = eng.metrics()
    assert m["latency"]["count"] == 2
    assert m["latency_hist"]["count"] == 2
    assert m["bucket_cache"]["hits"] == 1
    assert m["bucket_cache"]["misses"] == 1
    assert m["bucket_cache"]["resident"] == 1
    assert isinstance(m["registry"], dict)
    json.dumps(m, allow_nan=False)


def test_trace_disabled_is_null_span_and_records_nothing():
    obs.clear_trace()
    with obs.span("t.nothing", a=1) as sp:
        sp.set(b=2)
    assert obs.trace_events() == []
    assert obs.span("x") is obs.span("y")  # one shared no-op object


def test_span_is_a_profiler_host_event_joined_by_id(tmp_path):
    from jax.profiler import ProfileData

    obs.configure(trace=True)
    obs.clear_trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("t.parent", rows=3, kind="kde"):
            with obs.span("t.child") as child:
                child.set(late=1)
    finally:
        jax.profiler.stop_trace()
    ring = {e["name"]: e for e in obs.trace_events()}
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = {e.name: e
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("t.")}
    assert set(host) == {"t.parent", "t.child"}
    parent, child = (dict(host[n].stats) for n in ("t.parent", "t.child"))
    assert parent["span_id"] == ring["t.parent"]["id"]
    assert parent["parent_id"] == 0 and ring["t.parent"]["parent"] is None
    assert child["span_id"] == ring["t.child"]["id"]
    assert child["parent_id"] == ring["t.parent"]["id"]
    assert parent["rows"] == 3 and parent["kind"] == "kde"
    assert "late" not in child                  # attributes at entry only
    p, c = host["t.parent"], host["t.child"]
    assert p.start_ns <= c.start_ns
    assert c.start_ns + c.duration_ns <= p.start_ns + p.duration_ns


def test_disabled_span_constructs_no_profiler_annotation(monkeypatch):
    from repro.obs import trace

    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "TraceAnnotation", Counting)
    with obs.span("t.off", a=1):
        pass
    assert made == [] and obs.span("t.x") is trace._NULL_SPAN
    obs.configure(trace=True)
    with obs.span("t.on"):
        pass
    assert made == ["t.on"]


def test_annotate_is_no_longer_exported():
    assert not hasattr(obs, "annotate") and "annotate" not in obs.__all__


# ---------------------------------------------------------------------------
# Streaming: staleness histogram agrees with the engine's summary.
# ---------------------------------------------------------------------------


def _stream_cfg(**kw):
    base = dict(backend="pallas", method="sdkde", interpret=True,
                block_m=8, block_n=64, min_batch=16, max_batch=128,
                stream=True, staleness_budget=2)
    base.update(kw)
    return ServeConfig(**base)


def test_staleness_histogram_matches_summary(data):
    x, xa, y = data
    obs.registry.reset()
    eng = ServeEngine(_stream_cfg())
    eng.register("s", x[:128], h=H)
    _q(eng, "s", y[:8])
    for i in range(3):
        eng.registry.append("s", xa[i * 8:(i + 1) * 8])
        _q(eng, "s", y[:8])
    summ = eng.staleness_summary()
    hist = obs.histogram("serve.staleness_gen").snapshot()
    assert summ["count"] == hist["count"] >= 4
    assert summ["max"] == pytest.approx(hist["max"])
    # quantile estimate agrees to histogram resolution: exact when every
    # lag is 0; otherwise bounded by the winning bucket (lags 0 and 1
    # share the first bucket at lo=1, so the floor there is just >= 0)
    ratio = 10 ** (1 / 8)
    if summ["max"] == 0:
        assert hist["p50"] == 0.0
    else:
        assert 0.0 <= hist["p50"] <= max(summ["p50"], 1) * ratio


# ---------------------------------------------------------------------------
# Acceptance: the streaming soak's trace reconstructs every request chain.
# ---------------------------------------------------------------------------


def test_streaming_soak_trace_reconstruction(data):
    x, xa, y = data
    obs.configure(trace=True)
    obs.clear_trace()
    obs.registry.reset()
    # prune=0.0: an explicit epsilon engages the pruned pallas path at any
    # size, so per-request kernel launches appear in the trace
    eng = ServeEngine(_stream_cfg(prune=0.0))
    eng.register("soak", x[:128], h=H)
    rng = np.random.default_rng(0)
    n_requests = 6
    for i in range(n_requests):
        if i % 2 == 0:
            eng.registry.append("soak", xa[(i // 2) * 8:(i // 2) * 8 + 8])
        m = int(rng.integers(3, 60))
        _q(eng, "soak", y[:m])
    eng.registry.get("soak").stream.ensure(0)      # final flush

    ev = eng.trace_events()
    tree = obs.span_tree(ev)
    requests = [e for e in ev if e["name"] == "serve.request"]
    assert len(requests) == n_requests
    for req in requests:
        # request -> dispatch: staleness + pinned generation
        disp = [c for c in tree.get(req["id"], ())
                if c["name"] == "serve.dispatch"]
        assert len(disp) == 1, "each request has exactly one dispatch"
        a = disp[0]["attrs"]
        assert a["backend"] == "pallas"
        assert 0 <= a["staleness"] <= 2            # within budget
        assert "stream_gen" in a and "layout_epoch" in a
        # dispatch -> bucket: padded shape + cache hit/miss
        buck = [c for c in tree.get(disp[0]["id"], ())
                if c["name"] == "serve.bucket"]
        assert len(buck) == 1
        b = buck[0]["attrs"]
        assert b["bucket"] >= b["rows"] == req["attrs"]["rows"]
        assert b["cache"] in ("hit", "miss")
        assert b["pad_ratio"] == pytest.approx(b["bucket"] / b["rows"],
                                               rel=1e-3)
        # bucket -> pruned pass -> kernel launch: per-request occupancy
        pas = [c for c in tree.get(buck[0]["id"], ())
               if c["name"] == "kernels.prune.pass"]
        assert len(pas) == 1, "one pruned pass under each bucket span"
        assert pas[0]["attrs"]["rows"] == b["rows"]
        kern = [c for c in tree.get(pas[0]["id"], ())
                if c["name"] == "kernels.pruned_eval"]
        assert kern, "pruned launch span missing under bucket span"
        assert 0.0 < kern[0]["attrs"]["occupancy"] <= 1.0
    # the append/flush side of the soak is in the same trace
    names = {e["name"] for e in ev}
    assert {"stream.append", "stream.flush"} <= names
    # and the metrics plane saw the same story
    snap = obs.metrics_snapshot()
    assert snap["serve.staleness_gen"]["count"] == n_requests
    assert any(k.startswith("kernels.prune.launches") for k in snap)
    assert snap["kernels.prune.visit_fraction"]["count"] >= n_requests
