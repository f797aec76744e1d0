"""The ``ring`` backend: SD-KDE with rows sharded over four devices.

A child process sees four forced host devices (the main test process must
keep seeing one), runs ``SDKDE(backend="ring")`` with the Pallas kernels
interpreted, pruning forced at epsilon 0 so the pruned path runs, and
reports what the tests below assert:

  * the densities against the plain reference (``core/kde.py`` at
    ``jax.default_matmul_precision("highest")``) at f32 and bf16x2;
  * each row against the one-device ``pallas`` path on the same layout and
    tiles, pruned and dense: the same row tiles, visit lists and column
    order give the same bits, so equality is exact;
  * one device (this process) against the ``pallas`` path;
  * the sharded pass's spans, and a launch on every device;
  * no implicit copy between devices (JAX's transfer guard): each chip's
    programs read only what was put on that chip before any kernel ran,
    so no chip waits behind another's kernels.

n = 3001 divides neither the four devices nor either tile; n = 150 leaves
some devices with nothing but sentinel rows.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

N, D, M = 3001, 16, 256
TILES = dict(block_m=64, block_n=256)

_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro import obs
from repro.core import kde as ref
from repro.core.estimator import SDKDE, EstimatorConfig
from repro.distributed import shard

N, D, M, TILES = json.loads(sys.argv[1])
out = {"devices": len(jax.devices())}
kx, ky = jax.random.split(jax.random.PRNGKey(0))
x = jax.random.normal(kx, (N, D)).at[: N // 2].add(3.0)
y = 1.2 * jax.random.normal(ky, (M, D))


def job(backend, x, **kw):
    est = SDKDE(config=EstimatorConfig(backend=backend, **TILES, **kw))
    est.fit(x)
    return est, est.evaluate(y)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    r = np.abs(got - want) / np.maximum(np.abs(want), 1e-6 * want.max())
    return [float(r.max()), float(np.median(r))]


obs.configure(metrics=True, trace=True)
for precision in ("f32", "bf16x2"):
    obs.clear_trace()
    # every array a chip's programs read is put there explicitly: an
    # implicit copy from the first chip would queue behind its kernels
    with jax.transfer_guard_device_to_device("disallow"):
        est, dens = job("ring", x, precision=precision, prune=0.0)
    events = obs.trace_events()
    pal, pal_dens = job("pallas", x, precision=precision, prune=0.0)
    with jax.default_matmul_precision("highest"):
        want = ref.sdkde_eval(x, y, est.h, block=512)
    out[precision] = {
        "ref": rel(dens, want),
        "x_sd_equal": bool(np.array_equal(est.x_sd, pal.x_sd)),
        "rows_equal": bool(np.array_equal(dens, pal_dens)),
        "on_first": [str(a.devices()) == str({jax.devices()[0]})
                     for a in (est.x_sd, dens)],
    }
    if precision == "f32":
        by_id = {e["id"]: e["name"] for e in events}
        out["spans"] = [[e["name"], e["attrs"], by_id.get(e["parent"])]
                        for e in events]

with jax.transfer_guard_device_to_device("disallow"):
    est, dens = job("ring", x, prune="off")
pal, pal_dens = job("pallas", x, prune="off")
out["dense_equal"] = [bool(np.array_equal(est.x_sd, pal.x_sd)),
                      bool(np.array_equal(dens, pal_dens))]

# a second draw of as many queries compiles nothing new: the query
# layout's length depends on the count alone
compiled = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: compiled.append(name)
    if name == "/jax/core/compile/backend_compile_duration" else None)
est, _ = job("ring", x, prune=0.0)
# (drawn near one mode: fewer clusters, a shorter layout to each draw)
y2 = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (M, D)) + 3.0
with jax.default_matmul_precision("highest"):
    want2 = ref.sdkde_eval(x, y2, est.h, block=512)
compiled.clear()
dens2 = est.evaluate(y2)
out["new_draw"] = {"compiles": len(compiled), "ref": rel(dens2, want2)}

small = x[:150]
est, dens = job("ring", small, prune=0.0)
pal, pal_dens = job("pallas", small, prune=0.0)
out["small_equal"] = bool(np.array_equal(dens, pal_dens))
snap = obs.metrics_snapshot()
out["transfers"] = snap[shard.TRANSFER_BYTES]["count"]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps([N, D, M, TILES])],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert lines, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["devices"] == 4
    return out


def test_sizes_divide_neither_the_devices_nor_the_tiles():
    assert N % 4 and N % TILES["block_m"] and N % TILES["block_n"]


def test_f32_matches_the_plain_reference(child):
    # f32 accumulation over ~3000 terms and the norms-minus-Gram distance
    # put the kernels' error near 3e-6 here (max over rows, relative,
    # floored at 1e-6 of the peak); 1e-5 leaves room and still fails the
    # bf16x2 tier below
    mx, med = child["f32"]["ref"]
    assert mx <= 1e-5 and med <= 1e-6


def test_bf16x2_matches_the_plain_reference(child):
    # two bf16 planes carry ~16 mantissa bits: ~5e-5 here, the serve
    # layer's bar for the tier is 5e-4; it must read worse than f32
    mx, med = child["bf16x2"]["ref"]
    assert mx <= 2e-4 and med <= 2e-5
    assert mx > child["f32"]["ref"][0]


@pytest.mark.parametrize("precision", ["f32", "bf16x2"])
def test_each_row_is_the_one_device_pallas_path(child, precision):
    got = child[precision]
    assert got["x_sd_equal"] and got["rows_equal"]


def test_dense_rows_are_the_one_device_pallas_path(child):
    assert child["dense_equal"] == [True, True]


def test_one_device_is_the_pallas_path():
    # this process sees one device
    import jax

    from repro.core.estimator import SDKDE, EstimatorConfig

    assert len(jax.devices()) == 1
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (1500, D)).at[:700].add(3.0)
    y = jax.random.normal(ky, (100, D))
    got = {}
    for backend in ("ring", "pallas"):
        est = SDKDE(config=EstimatorConfig(backend=backend, prune=0.0,
                                           **TILES)).fit(x)
        got[backend] = (np.asarray(est.x_sd), np.asarray(est.evaluate(y)))
    for a, b in zip(got["ring"], got["pallas"]):
        np.testing.assert_array_equal(a, b)


def test_a_new_draw_of_queries_compiles_nothing(child):
    got = child["new_draw"]
    assert got["compiles"] == 0
    assert got["ref"][0] < 1e-4


def test_query_layout_length_depends_on_the_count_alone():
    """Any draw of m queries over k clusters fits the sharded eval's query
    layout, which is therefore one length for every draw; the worst case,
    every cluster one row past whole tiles, fills it."""
    from repro.distributed import shard
    from repro.kernels import spatial

    rng = np.random.default_rng(0)
    k, bm, chips = 37, 64, 4
    m = k * (bm + 1)
    rows = shard._query_rows(m, k, bm, chips)
    assert rows % (bm * chips) == 0
    worst = np.repeat(np.arange(k), bm + 1)
    draws = [worst] + [rng.choice(k, size=m, p=rng.dirichlet(np.full(k, a)))
                       for a in (100.0, 1.0, 0.01)]
    for labels in draws:
        y = rng.normal(size=(m, 3)).astype(np.float32)
        lay = spatial.cluster_layout(y, labels, bm, total_multiple=rows)
        assert lay.points.shape[0] == rows
    _, caps = spatial.cluster_capacities(worst, bm)
    assert rows - caps.sum() < bm * chips


def test_devices_left_with_sentinel_rows_only(child):
    assert child["small_equal"]


def test_answers_come_back_to_the_first_device(child):
    assert child["f32"]["on_first"] == [True, True]


def test_sharded_passes_have_their_spans(child):
    spans = child["spans"]
    passes = [a for n, a, _ in spans if n == "distributed.shard.pass"]
    assert passes == [{"kind": "score", "chips": 4, "rows": N, "cols": N},
                      {"kind": "kde", "chips": 4, "rows": M, "cols": N}]
    dispatch = [a for n, a, _ in spans if n == "distributed.shard.dispatch"]
    assert [a["chip"] for a in dispatch] == [0, 1, 2, 3] * 2
    assert sum(a["rows"] for a in dispatch[:4]) == N
    assert sum(a["rows"] for a in dispatch[4:]) == M
    for name in ("kernels.pruned_score", "kernels.pruned_eval"):
        launches = [a for n, a, _ in spans if n == name]
        assert [a["chip"] for a in launches] == [0, 1, 2, 3]
    gathers = [(a["bytes"], p) for n, a, p in spans
               if n == "distributed.shard.gather"]
    assert len(gathers) == 4 and all(b > 0 for b, _ in gathers)
    assert child["transfers"] > 0


def test_the_host_steps_sit_under_a_pruned_pass(child):
    """The pruning layer's readers find the sharded passes' host steps:
    each pass's steps and per-chip dispatches lie under a
    ``kernels.prune.pass``, which closes before the wait for the chips."""
    spans = child["spans"]
    passes = [(a, p) for n, a, p in spans if n == "kernels.prune.pass"]
    assert passes == [
        ({"kind": "score", "rows": N, "cols": N}, "distributed.shard.pass"),
        ({"kind": "kde", "rows": M, "cols": N}, "distributed.shard.pass")]
    assert all(p == "kernels.prune.pass" for n, _, p in spans
               if n == "distributed.shard.dispatch")
    assert {n for n, _, p in spans if p == "distributed.shard.dispatch"} \
        == {"kernels.prune.visit_lists", "kernels.prune.profile",
            "kernels.pruned_score", "kernels.pruned_eval"}
    # placing the parts, under the pass; collecting them, after it
    assert [p for n, _, p in spans if n == "distributed.shard.gather"] == [
        "kernels.prune.pass", "distributed.shard.pass"] * 2
