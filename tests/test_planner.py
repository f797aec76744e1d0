"""Planner conformance: golden decisions, validity properties, wiring.

Three layers:

1. Golden-decision snapshots — one pinned plan per committed benchmark
   cell (``tests/golden_plans.json``).  Any drift fails; the fixture is
   rewritten only deliberately via ``python -m repro.plan --regen-golden``.
2. Property suite — randomized (n, d, q, accuracy, backend, stream)
   requests always produce *valid* plans: VMEM-fitting blocks, tile
   multiples, tier/prune compatibility, monotone modeled cost in n.
   Uses hypothesis when available, a fixed-seed sweep otherwise (same
   degradation pattern as tests/test_pruning.py).
3. Wiring — override precedence in ``resolve_config``, the ops ``plan=``
   kwarg, engine prewarm, plan-decision metrics, eps=0 plans dense.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.kernels import autotune, ops
from repro.kernels import precision as prec
from repro.plan import (
    DEFAULT_ACCURACY,
    EPS_SAFETY,
    PALLAS_MIN_COLS,
    TIER_RTOL,
    BenchModel,
    ExecutionPlan,
    PlanRequest,
    golden_entries,
    load_docs,
    load_golden,
    plan,
    plan_for,
    request_key,
    requests_from_docs,
    resolve_config,
)
from repro.serve import QueryRequest, ServeConfig, ServeEngine


def _q(eng, key, y, **kw):
    """One typed query, densities out."""
    return eng.query(QueryRequest(key=key, points=y, **kw)).value

_REPO = Path(__file__).resolve().parents[1]
_GOLDEN = load_golden(_REPO / "tests" / "golden_plans.json")
_BENCH = BenchModel.load()


def _subenv():
    env = dict(os.environ)
    src = str(_REPO / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# Golden-decision conformance.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recomputed():
    """Planner decisions recomputed fresh from the committed artifacts."""
    return golden_entries()


def test_golden_fixture_meta():
    assert _GOLDEN["meta"]["entries"] == len(_GOLDEN["plans"])
    assert _GOLDEN["meta"]["entries"] >= 15
    assert "--regen-golden" in _GOLDEN["meta"]["regen"]


def test_golden_covers_every_committed_cell():
    """Every shape-bearing benchmark cell derives a pinned request, and
    the fixture has no stale extras — derivation drift fails here."""
    want = {request_key(r) for r in requests_from_docs(load_docs())}
    assert want == set(_GOLDEN["plans"])


@pytest.mark.parametrize("key", sorted(_GOLDEN["plans"]))
def test_golden_decision(key, recomputed):
    """The planner's decision for this cell matches the pinned plan."""
    assert key in recomputed, f"no longer derived: {key}"
    pinned, fresh = _GOLDEN["plans"][key], recomputed[key]
    assert fresh["request"] == pinned["request"]
    assert fresh["plan"] == pinned["plan"], (
        f"plan drift for {key} — if intentional, rerun "
        "`python -m repro.plan --regen-golden`"
    )


def test_golden_plans_all_valid(recomputed):
    for key, entry in recomputed.items():
        req = PlanRequest(**entry["request"])
        p = plan(req, bench=_BENCH)
        assert p.validate() == [], key


def test_plans_match_or_beat_default_path():
    """Acceptance bar: on every committed cell the planner's modeled cost
    is within the 15% regression gate of — in practice, well under — the
    current default serve path (f32 @ 128x512, prune auto)."""
    for key, entry in _GOLDEN["plans"].items():
        if entry["plan"]["backend"] != "pallas":
            continue
        r = entry["request"]
        default = autotune.modeled_cost(
            r["q"], r["n"], r["d"], block_m=128,
            block_n=min(512, r["n"]) if r["n"] >= 128 else 128,
            precision="f32", vmem_itemsize=4,
        )
        if default is None:
            continue
        got = entry["plan"]["modeled_cost_us"] * 1e-6
        assert got <= default.step_time * 1.15, (
            f"{key}: planned {got * 1e6:.1f}us worse than default "
            f"{default.step_time * 1e6:.1f}us beyond the 15% gate"
        )


# ---------------------------------------------------------------------------
# Regen CLI (the deliberate-rewrite path).
# ---------------------------------------------------------------------------


def test_regen_cli_reproduces_committed_fixture(tmp_path):
    """--regen-golden writes a byte-stable fixture identical to the
    committed one (i.e. the committed fixture is up to date)."""
    out = tmp_path / "golden.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.plan", "--regen-golden",
         "--golden", str(out)],
        capture_output=True, text=True, env=_subenv(), timeout=300,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(out.read_text()) == _GOLDEN
    committed = (_REPO / "tests" / "golden_plans.json").read_text()
    assert out.read_text() == committed


def test_cli_adhoc_plan_json():
    r = subprocess.run(
        [sys.executable, "-m", "repro.plan", "--n", "262144", "--d", "16",
         "--q", "32768"],
        capture_output=True, text=True, env=_subenv(), timeout=300,
    )
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["plan"]["backend"] == "pallas"
    assert doc["plan"]["block_m"] % 8 == 0
    assert "/" in doc["plan_id"]


def test_cli_requires_shape_or_regen():
    r = subprocess.run(
        [sys.executable, "-m", "repro.plan"],
        capture_output=True, text=True, env=_subenv(), timeout=300,
    )
    assert r.returncode != 0


# ---------------------------------------------------------------------------
# Decision rules (unit).
# ---------------------------------------------------------------------------


def test_tier_admissibility():
    assert plan_for(4096, 8, accuracy=1e-5, bench=_BENCH).precision == "f32"
    # tighter than f32's own bar still resolves (f32 is the reference)
    assert plan_for(4096, 8, accuracy=1e-9, bench=_BENCH).precision == "f32"
    loose = plan_for(4096, 8, accuracy=5e-2, bench=_BENCH)
    assert TIER_RTOL[loose.precision] <= 5e-2
    mid = plan_for(4096, 8, accuracy=5e-4, bench=_BENCH)
    assert mid.precision in ("f32", "bf16x2")


def test_backend_auto_routing():
    assert plan_for(PALLAS_MIN_COLS - 1, 8, bench=_BENCH).backend == "jnp"
    assert plan_for(PALLAS_MIN_COLS, 8, bench=_BENCH).backend == "pallas"


def test_backend_explicit_honored():
    p = plan_for(262144, 16, backend="jnp", bench=_BENCH)
    assert p.backend == "jnp"
    assert p.prune == "off" and p.block_m is None
    assert plan_for(64, 4, backend="pallas", bench=_BENCH).backend == "pallas"
    r = plan_for(8192, 8, backend="ring", bench=_BENCH)
    assert r.backend == "ring" and r.prune == "off"
    # "auto" never routes to the ring — multi-host is an explicit choice
    for n in (64, 8192, 1 << 20):
        assert plan_for(n, 8, bench=_BENCH).backend != "ring"


def test_prune_off_below_threshold():
    p = plan_for(ops.PRUNE_AUTO_MIN_COLS - 1, 16, bench=_BENCH)
    assert p.prune == "off"


def test_prune_promoted_by_measured_cells():
    # the committed 262144x16 pruning sweep measured eps up to 1e-6 at
    # zero observed error; accuracy 1e-5 licenses eps<=1e-7 -> 1e-9 wins
    p = plan_for(262144, 16, q=32768, accuracy=1e-5, bench=_BENCH)
    assert p.prune == pytest.approx(1e-9)
    assert p.occupancy < 1.0            # measured occupancy priced in
    # a looser target promotes the larger measured epsilon
    p4 = plan_for(262144, 16, q=32768, accuracy=1e-4, bench=_BENCH)
    assert p4.prune == pytest.approx(1e-6)
    assert p4.modeled_cost_s <= p.modeled_cost_s


def test_prune_unmeasured_regime_stays_exact():
    # no committed pruning cells for this regime: epsilon>0 is never
    # licensed, only exact (certified-underflow) pruning
    p = plan_for(65536, 3, accuracy=5e-2, bench=_BENCH)
    assert p.prune == pytest.approx(0.0)


def test_prune_epsilon_accuracy_rule():
    for acc in (1e-5, 1e-4, 1e-3, 5e-2):
        p = plan_for(262144, 16, accuracy=acc, bench=_BENCH)
        if isinstance(p.prune, float) and p.prune > 0:
            assert p.prune * EPS_SAFETY <= acc


def test_staleness_policy():
    assert plan_for(4096, 8, bench=_BENCH).staleness_budget == 0
    s0 = plan_for(4096, 8, stream=True, accuracy=1e-5, bench=_BENCH)
    assert s0.staleness_budget == 0 and not s0.stream_background
    s1 = plan_for(4096, 8, stream=True, accuracy=5e-4, bench=_BENCH)
    assert s1.staleness_budget == 1 and s1.stream_background
    s2 = plan_for(4096, 8, stream=True, accuracy=5e-2, bench=_BENCH)
    assert s2.staleness_budget == 2 and s2.stream_background


def test_monotone_cost_in_n():
    """Doubling the train count never makes the planned pass cheaper."""
    empty = BenchModel()
    for d, q in ((2, 256), (16, 1024), (64, 256)):
        prev = 0.0
        for n in (64, 256, 1024, 2048, 4096, 16384, 65536, 262144):
            c = plan_for(n, d, q=q, bench=empty).modeled_cost_s
            assert c >= prev, (d, q, n)
            prev = c


# ---------------------------------------------------------------------------
# Plan validity (schema-level).
# ---------------------------------------------------------------------------


def _mk(req, **kw):
    base = dict(request=req, backend="pallas", precision="f32",
                prune="off", block_m=8, block_n=128,
                modeled_cost_s=1e-6, bound="vpu")
    base.update(kw)
    return ExecutionPlan(**base)


def test_validate_block_multiples():
    req = PlanRequest(n=4096, d=8)
    assert any("multiple of 8" in p
               for p in _mk(req, block_m=12).validate())
    assert any("multiple of 128" in p
               for p in _mk(req, block_n=200).validate())
    assert _mk(req).validate() == []


def test_validate_vmem_budget():
    req = PlanRequest(n=65536, d=512)
    bad = _mk(req, block_m=2048, block_n=4096)
    assert any("VMEM" in p or "vmem" in p for p in bad.validate())


def test_validate_epsilon_budget():
    req = PlanRequest(n=65536, d=8, accuracy=1e-5)
    bad = _mk(req, prune=1e-6)           # 1e-6 * 100 > 1e-5
    assert any("epsilon" in p for p in bad.validate())
    assert _mk(req, prune=1e-8).validate() == []
    assert any("< 0" in p for p in _mk(req, prune=-1.0).validate())


def test_validate_tier_vs_accuracy():
    req = PlanRequest(n=4096, d=8, accuracy=1e-5)
    bad = _mk(req, precision="bf16")
    assert any("exceeds accuracy" in p for p in bad.validate())


def test_validate_backend_constraints():
    req = PlanRequest(n=4096, d=8)
    jnp_pruned = ExecutionPlan(request=req, backend="jnp",
                               precision="f32", prune=0.0)
    assert any("pallas" in p for p in jnp_pruned.validate())
    stale = ExecutionPlan(request=req, backend="jnp", precision="f32",
                          prune="off", staleness_budget=1)
    assert any("staleness" in p for p in stale.validate())
    with pytest.raises(ValueError, match="invalid execution plan"):
        jnp_pruned.check()


def test_plan_request_validation():
    with pytest.raises(ValueError):
        PlanRequest(n=0, d=8)
    with pytest.raises(ValueError):
        PlanRequest(n=8, d=8, accuracy=0.0)
    with pytest.raises(ValueError):
        PlanRequest(n=8, d=8, backend="tpu")


def test_plan_id_stable_format():
    p = plan_for(262144, 16, q=32768, bench=_BENCH)
    assert p.plan_id == "pallas/f32/prune=1e-09/2048x128"
    assert plan_for(64, 4, bench=_BENCH).plan_id == "jnp/f32/prune=off/-"


# ---------------------------------------------------------------------------
# Property suite: randomized requests are always valid.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # degrades to a fixed-seed sweep
    _HAVE_HYPOTHESIS = False


_ACCURACIES = [1e-6, 1e-5, 1e-4, 5e-4, 1e-2, 5e-2, 1.0]


def _valid_plan_case(n, d, q, accuracy, backend, stream):
    req = PlanRequest(n=n, d=d, q=q, accuracy=accuracy,
                      backend=backend, stream=stream)
    p = plan(req, bench=_BENCH)
    assert p.validate() == []
    if p.backend == "pallas":
        assert p.block_m % 8 == 0
        assert p.block_n % 128 == 0
        ops._check_vmem(p.block_m, p.block_n, d, itemsize=4, out_width=1)
    else:
        assert p.prune == "off"
        assert p.block_m is None and p.block_n is None
    if isinstance(p.prune, float) and p.prune > 0:
        assert p.prune * EPS_SAFETY <= accuracy
    if not stream:
        assert p.staleness_budget == 0
    assert TIER_RTOL[p.precision] <= max(accuracy, TIER_RTOL["f32"])
    assert p.modeled_cost_s > 0


if _HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(
        n=st.integers(1, 1 << 20),
        d=st.integers(1, 128),
        q=st.integers(1, 16384),
        accuracy=st.sampled_from(_ACCURACIES),
        backend=st.sampled_from(["auto", "jnp", "pallas"]),
        stream=st.booleans(),
    )
    def test_random_plans_always_valid(n, d, q, accuracy, backend, stream):
        _valid_plan_case(n, d, q, accuracy, backend, stream)

else:

    @pytest.mark.parametrize("seed", range(10))
    def test_random_plans_always_valid(seed):
        rng = np.random.default_rng(seed)
        for _ in range(8):
            _valid_plan_case(
                n=int(rng.integers(1, 1 << 20)),
                d=int(rng.integers(1, 129)),
                q=int(rng.integers(1, 16385)),
                accuracy=float(rng.choice(_ACCURACIES)),
                backend=str(rng.choice(["auto", "jnp", "pallas"])),
                stream=bool(rng.integers(0, 2)),
            )


# ---------------------------------------------------------------------------
# eps=0 plans are dense (the pruning oracle, via the plan= kwarg).
# ---------------------------------------------------------------------------


def _eps0_dense_case(seed, h, visit_every_tile, f64, assert_as_accurate):
    rng = np.random.default_rng(seed)
    x = np.asarray(rng.normal(size=(512, 3)), np.float32)
    y = np.asarray(rng.normal(size=(96, 3)), np.float32)
    req = PlanRequest(n=512, d=3, q=96, backend="pallas")
    p = _mk(req, prune=0.0, block_m=8, block_n=128).check()
    pruned = ops.flash_kde(x, y, h, interpret=True, plan=p)
    dense = ops.flash_kde(x, y, h, interpret=True, prune="off",
                          block_m=8, block_n=128)
    # f32 at d=3: the pruned kernels pack their GEMMs and the dense ones
    # run HIGHEST, so the pruned sums match the same kernels over every
    # tile to the eps=0 oracle bar of tests/test_pruning.py (identical up
    # to summation order), and the dense sums as closely as f32 allows
    assert p.precision == "f32" and prec.packs(p.precision)
    with visit_every_tile():
        every = ops.flash_kde(x, y, h, interpret=True, plan=p)
    np.testing.assert_allclose(np.asarray(pruned), np.asarray(every),
                               rtol=1e-6, atol=1e-20)
    assert_as_accurate(pruned, dense, f64.kde(x, y, h))


if _HAVE_HYPOTHESIS:

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10_000), h=st.floats(0.1, 1.0))
    def test_eps0_plan_is_dense(seed, h, visit_every_tile, f64,
                                assert_as_accurate):
        _eps0_dense_case(seed, h, visit_every_tile, f64, assert_as_accurate)

else:

    @pytest.mark.parametrize("seed,h", [(0, 0.3), (1, 0.8), (2, 0.15)])
    def test_eps0_plan_is_dense(seed, h, visit_every_tile, f64,
                                assert_as_accurate):
        _eps0_dense_case(seed, h, visit_every_tile, f64, assert_as_accurate)


# ---------------------------------------------------------------------------
# Wiring: resolve_config precedence, ops plan kwarg, prewarm, metrics.
# ---------------------------------------------------------------------------


def test_resolve_config_fills_defaults():
    cfg = ServeConfig(plan="auto", min_batch=16, max_batch=128)
    resolved, p = resolve_config(cfg, n=262144, d=16, bench=_BENCH)
    assert p.validate() == []
    assert resolved.backend == p.backend == "pallas"
    assert resolved.precision == p.precision
    assert resolved.prune == p.prune
    assert resolved.block_m == p.block_m
    assert resolved.block_n == p.block_n
    assert p.request.q == 128            # q = the config's max_batch


def test_resolve_config_explicit_wins():
    cfg = ServeConfig(plan="auto", backend="ring", block_m=64,
                      min_batch=16, max_batch=128)
    resolved, p = resolve_config(cfg, n=262144, d=16, bench=_BENCH)
    # explicitly-set (non-default) knobs survive plan resolution untouched
    assert resolved.backend == "ring" == p.backend
    assert resolved.block_m == 64
    assert resolved.prune == "off"       # non-pallas plans never prune


def test_resolve_config_default_value_reads_as_unset():
    # setting a knob TO its dataclass default is indistinguishable from
    # not setting it — the planner owns it (pass plan="off" to pin all)
    cfg = ServeConfig(plan="auto", backend="jnp",
                      min_batch=16, max_batch=128)
    resolved, p = resolve_config(cfg, n=262144, d=16, bench=_BENCH)
    assert resolved.backend == "pallas" == p.backend


def test_resolve_config_accuracy_target():
    cfg = ServeConfig(plan="auto", accuracy_target=1e-4,
                      min_batch=16, max_batch=128)
    _, p = resolve_config(cfg, n=262144, d=16, bench=_BENCH)
    assert p.request.accuracy == 1e-4
    assert p.prune == pytest.approx(1e-6)


def test_serve_config_plan_validation():
    with pytest.raises(ValueError, match="plan"):
        ServeConfig(plan="maybe")
    with pytest.raises(ValueError, match="accuracy_target"):
        ServeConfig(plan="auto", accuracy_target=-1.0)


def test_ops_plan_kwarg_matches_explicit_knobs():
    rng = np.random.default_rng(3)
    x = np.asarray(rng.normal(size=(2048, 4)), np.float32)
    y = np.asarray(rng.normal(size=(64, 4)), np.float32)
    p = plan_for(2048, 4, q=64, backend="pallas", bench=_BENCH)
    a = ops.flash_kde(x, y, 0.5, interpret=True, plan=p)
    b = ops.flash_kde(x, y, 0.5, interpret=True, precision=p.precision,
                      block_m=p.block_m, block_n=p.block_n, prune=p.prune)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-7)


def test_ops_plan_auto_resolves_per_call():
    rng = np.random.default_rng(4)
    x = np.asarray(rng.normal(size=(2048, 4)), np.float32)
    y = np.asarray(rng.normal(size=(32, 4)), np.float32)
    a = ops.flash_kde(x, y, 0.5, interpret=True)
    b = ops.flash_kde(x, y, 0.5, interpret=True, plan="auto")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_engine_prewarm_builds_chosen_executable():
    rng = np.random.default_rng(5)
    x = np.asarray(rng.normal(size=(512, 4)), np.float32)
    cfg = ServeConfig(plan="auto", min_batch=16, max_batch=64)
    eng = ServeEngine(cfg)
    prep = eng.register("warm", x)
    assert prep.plan is not None
    assert len(eng.cache) == 1           # largest bucket built at register
    misses = eng.cache.misses
    _q(eng, "warm", x[:64])
    assert eng.cache.misses == misses    # served by the prewarmed program


def test_plan_decision_metrics_emitted():
    before = {k: v for k, v in obs.metrics_snapshot().items()
              if k.startswith("plan.decisions")}
    p = plan_for(262144, 16, q=32768, bench=_BENCH)
    key = (f"plan.decisions{{backend={p.backend},prune=eps,"
           f"tier={p.precision}}}")
    after = obs.metrics_snapshot()
    assert after[key]["value"] >= before.get(key, {}).get("value", 0) + 1


def test_dispatch_span_carries_plan_id():
    rng = np.random.default_rng(6)
    x = np.asarray(rng.normal(size=(512, 4)), np.float32)
    obs.configure(trace=True)
    try:
        eng = ServeEngine(ServeConfig(plan="auto", min_batch=16,
                                      max_batch=64))
        prep = eng.register("traced", x)
        _q(eng, "traced", x[:8])
        spans = [e for e in eng.trace_events()
                 if e.get("name") == "serve.dispatch"
                 and e.get("attrs", {}).get("key") == "traced"]
        assert spans
        assert spans[-1]["attrs"]["plan"] == prep.plan.plan_id
    finally:
        obs.configure(trace=False)
