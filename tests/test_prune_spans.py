"""The pruned path's host steps as spans, and its device-to-host syncs.

One small ``flash_sdkde`` with pruning forced at epsilon 0 and the Pallas
kernels interpreted: every host step of each pruned pass runs in its own
``kernels.prune.*`` span under ``kernels.prune.pass``, and every fetch of a
device array to the host is one observation of
``kernels.prune.host_sync_bytes``.  ``block_n`` is the tuner's fine probe
width, so the probe's extra bounds pass (which runs only until the tuner's
profile holds a record) never runs and the sync count is fixed.
"""

import jax
import pytest

from repro import obs
from repro.kernels import autotune, ops, spatial

N, M, D, H = 1024, 96, 4, 0.4
BLOCK_M, BLOCK_N = 32, autotune.FINE_PROBE_BLOCK

SCORE_STEPS = ["kernels.prune.index", "kernels.prune.layout",
               "kernels.prune.operands", "kernels.prune.tile_map",
               "kernels.prune.visit_lists", "kernels.prune.profile",
               "kernels.pruned_score", "kernels.prune.gather"]
KDE_STEPS = ["kernels.prune.columns", "kernels.prune.layout",
             "kernels.prune.operands", "kernels.prune.tile_map",
             "kernels.prune.visit_lists", "kernels.prune.profile",
             "kernels.pruned_eval", "kernels.prune.gather"]
#: Fetches per flash_sdkde: the score pass pulls its labels, keep matrix
#: and certificate maximum; the kde pass its train labels, query labels,
#: keep matrix, real-column count and certificate maximum.
SYNCS = 8


@pytest.fixture(autouse=True)
def _traced():
    m0, t0 = obs.state.metrics_on, obs.state.trace_on
    obs.configure(metrics=True, trace=True)
    obs.clear_trace()
    yield
    obs.configure(metrics=m0, trace=t0)
    obs.clear_trace()


@pytest.fixture(scope="module")
def data():
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (N, D))
    x = x.at[: N // 2].add(6.0)             # two far clusters: tiles skip
    y = jax.random.normal(ky, (M, D))
    return x, y


def _sdkde(data, prune):
    x, y = data
    return ops.flash_sdkde(x, y, H, block_m=BLOCK_M, block_n=BLOCK_N,
                           interpret=True, prune=prune).block_until_ready()


def _syncs():
    h = obs.metrics_snapshot().get(spatial.HOST_SYNC_BYTES)
    return (0, 0.0) if h is None else (h["count"], h["sum"])


def _children(tree, ev):
    return sorted(tree.get(ev["id"], ()), key=lambda e: e["ts_us"])


def test_each_pruned_pass_holds_its_steps_in_order(data):
    _sdkde(data, 0.0)
    ev = obs.trace_events()
    tree = obs.span_tree(ev)
    passes = sorted((e for e in ev if e["name"] == "kernels.prune.pass"),
                    key=lambda e: e["ts_us"])
    assert [p["attrs"] for p in passes] == [
        {"kind": "score", "rows": N, "cols": N},
        {"kind": "kde", "rows": M, "cols": N}]
    score, kde = (_children(tree, p) for p in passes)
    assert [c["name"] for c in score] == SCORE_STEPS
    assert [c["name"] for c in kde] == KDE_STEPS
    # the eval side reuses the score pass's clustering: no second index
    assert [c["name"] for c in _children(tree, kde[0])] == [
        "kernels.prune.layout", "kernels.prune.operands"]
    # each step's interval lies inside its pass
    for p, steps in ((passes[0], score), (passes[1], kde)):
        for c in steps:
            assert p["ts_us"] <= c["ts_us"]
            assert c["ts_us"] + c["dur_us"] <= p["ts_us"] + p["dur_us"]
    # the launches keep the names and attributes the benchmark reads
    assert score[6]["attrs"]["rows"] == N
    assert kde[6]["attrs"]["kind"] == "kde"
    assert 0.0 < kde[6]["attrs"]["occupancy"] < 1.0


def test_dense_launches_have_spans_and_no_pruned_steps(data):
    _sdkde(data, "off")
    names = [e["name"] for e in obs.trace_events()]
    assert names.count("kernels.dense_score") == 1
    assert names.count("kernels.dense_eval") == 1
    assert not any(n.startswith("kernels.prune") for n in names)
    dense = next(e for e in obs.trace_events()
                 if e["name"] == "kernels.dense_eval")
    assert dense["attrs"] == {"rows": M, "cols": N, "kind": "kde"}


def _sync_bytes_by_step():
    """{(pass kind, step path): sync_bytes} of the buffered spans."""
    ev = obs.trace_events()
    by_id = {e["id"]: e for e in ev}
    out = {}
    for e in ev:
        if "sync_bytes" not in e["attrs"]:
            continue
        path, p = [e["name"]], by_id.get(e["parent"])
        while p is not None and p["name"] != "kernels.prune.pass":
            path.insert(0, p["name"])
            p = by_id.get(p["parent"])
        out[(p["attrs"]["kind"], "/".join(path))] = e["attrs"]["sync_bytes"]
    return out


def test_every_host_sync_is_counted_once_and_repeats_exactly(data):
    _sdkde(data, 0.0)                        # compiles, fills the profile
    gained, steps = [], []
    for _ in range(2):
        obs.clear_trace()
        c0, b0 = _syncs()
        _sdkde(data, 0.0)
        c1, b1 = _syncs()
        gained.append((c1 - c0, b1 - b0))
        steps.append(_sync_bytes_by_step())
    assert gained[0] == gained[1] and steps[0] == steps[1]
    count, nbytes = gained[0]
    assert count == SYNCS
    # the bytes the histogram saw are the bytes the step spans carry
    got = steps[0]
    assert sum(got.values()) == nbytes
    layout, visits, profile = ("kernels.prune." + s for s in
                                ("layout", "visit_lists", "profile"))
    columns_layout = "kernels.prune.columns/" + layout
    assert set(got) == {("score", layout), ("score", visits),
                        ("score", profile), ("kde", columns_layout),
                        ("kde", layout), ("kde", visits), ("kde", profile)}
    assert got[("score", layout)] == N * 4       # int32 labels
    assert got[("kde", columns_layout)] == N * 4
    assert got[("kde", layout)] == M * 4
    assert got[("score", profile)] == 4          # f32 certificate maximum
    assert got[("kde", profile)] == 4 + 4        # + int32 real-column count
    # one bool per (row tile, column tile) of the padded layouts
    assert got[("score", visits)] >= (N // BLOCK_M) * (N // BLOCK_N)
    assert got[("kde", visits)] >= -(-M // BLOCK_M) * (N // BLOCK_N)


def test_score_launch_span_counts_its_grid_steps(data, monkeypatch):
    """``kernels.pruned_score`` carries the visit slots each grid step runs
    and the launch's grid steps: one per ``slots_per_step`` visit slots of
    every row tile, all row groups together."""
    from repro.kernels import flash_pruned

    lists = []
    compact = spatial.visit_lists

    def visit_lists(keep, **kw):
        lists.append(compact(keep, **kw))
        return lists[-1]

    monkeypatch.setattr(spatial, "visit_lists", visit_lists)
    _sdkde(data, 0.0)
    vl = lists[0]                             # the score pass's, first
    mt = vl.tile_map.shape[0]
    attrs = next(e["attrs"] for e in obs.trace_events()
                 if e["name"] == "kernels.pruned_score")
    slots = attrs["slots_per_step"]
    assert slots == flash_pruned.score_slots(
        vl.max_visits, block_m=BLOCK_M, block_n=BLOCK_N, d=D, itemsize=4)
    assert 1 < slots <= vl.max_visits
    assert attrs["grid_steps"] == mt * -(-vl.max_visits // slots)
