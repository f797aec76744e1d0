"""Substrate tests: optimizers, schedules, checkpointing, data pipeline,
density weighting, estimator API."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_pytree, save_pytree
from repro.core.estimator import KDE, SDKDE, LaplaceKDE, EstimatorConfig
from repro.data.density import DensityWeighting, density_weights
from repro.data.synthetic import PrefetchLoader, lm_batch
from repro.models.common import ModelConfig, init_params, param_shapes
from repro.models.transformer import loss_fn
from repro.optim import (
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    cosine_schedule,
    opt_state_pspecs,
)
from repro.optim.adafactor import (
    adafactor_init,
    adafactor_state_pspecs,
    adafactor_update,
)

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, dtype=jnp.float32, remat="none", loss_chunk=0)


# -- optimizers ----------------------------------------------------------------


def _run_steps(opt_init, opt_update, n=8):
    params = init_params(CFG, jax.random.PRNGKey(0))
    batch = lm_batch(CFG, 0, 0, 4, 16)
    state = opt_init(params)
    losses = []
    for step in range(n):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, CFG)
        grads, _ = clip_by_global_norm(grads, 1.0)
        params, state = opt_update(grads, state, params, 1e-2)
        losses.append(float(loss))
    return losses


def test_adamw_converges():
    losses = _run_steps(adamw_init, adamw_update)
    assert losses[-1] < losses[0] - 0.5, losses


def test_adafactor_converges():
    losses = _run_steps(adafactor_init, adafactor_update)
    assert losses[-1] < losses[0] - 0.5, losses


def test_adamw_bf16_params_keep_f32_master():
    import dataclasses

    cfg16 = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    params = init_params(cfg16, jax.random.PRNGKey(0))
    state = adamw_init(params)
    assert state["master"]["embed"].dtype == jnp.float32
    batch = lm_batch(cfg16, 0, 0, 2, 8)
    _, grads = jax.value_and_grad(loss_fn)(params, batch, cfg16)
    new_params, state = adamw_update(grads, state, params, 1e-3)
    assert new_params["embed"].dtype == jnp.bfloat16


def test_zero1_pspecs_extend_over_data():
    from jax.sharding import PartitionSpec as P

    specs = opt_state_pspecs(param_shapes(CFG), 4)
    # embed is P('model', None) -> master gains 'data' on the free dim
    assert specs["master"]["embed"] == P("model", "data")
    # tuple axis (multi-pod)
    specs = opt_state_pspecs(param_shapes(CFG), 8, axis=("pod", "data"))
    assert specs["master"]["embed"] == P("model", ("pod", "data"))


def test_adafactor_pspecs_structure():
    specs = adafactor_state_pspecs(param_shapes(CFG), 4)
    assert "vr" in specs["v"]["embed"]
    assert "v" in specs["v"]["final_norm"]


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.int32(s), 1e-3, 10, 100))
           for s in (0, 5, 10, 50, 100)]
    assert lrs[0] == 0.0
    assert abs(lrs[2] - 1e-3) < 1e-9
    assert lrs[3] < lrs[2]
    assert abs(lrs[4] - 1e-4) < 1e-6  # final_frac * peak


# -- checkpoint ------------------------------------------------------------------


def test_checkpoint_roundtrip_and_rotation():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        tree = {"p": {"w": jnp.arange(12.0).reshape(3, 4)},
                "step": jnp.int32(7)}
        for s in (10, 20, 30):
            mgr.save(s, tree, blocking=True)
        assert mgr.committed_steps() == [20, 30]
        out = mgr.restore()
        np.testing.assert_array_equal(out["p"]["w"], tree["p"]["w"])
        assert int(out["step"]) == 7


def test_checkpoint_ignores_torn_writes():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, {"x": jnp.ones(3)}, blocking=True)
        # torn: directory without _COMMITTED marker
        os.makedirs(os.path.join(d, "step_000000002"))
        assert mgr.latest_step() == 1


def test_checkpoint_restore_with_sharding():
    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    with tempfile.TemporaryDirectory() as d:
        save_pytree({"w": jnp.ones((4, 4))}, d)
        out = restore_pytree(
            d, {"w": NamedSharding(mesh, P("data", None))}
        )
        assert out["w"].sharding.spec == P("data", None)


# -- data ------------------------------------------------------------------------


def test_batches_deterministic_and_step_dependent():
    b1 = lm_batch(CFG, 3, 7, 4, 16)
    b2 = lm_batch(CFG, 3, 7, 4, 16)
    b3 = lm_batch(CFG, 3, 8, 4, 16)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    assert int(b1["tokens"].max()) < CFG.vocab_size


def test_zipf_tokens_skewed():
    toks = np.asarray(lm_batch(CFG, 0, 0, 64, 64)["tokens"]).ravel()
    # Zipf: low ids much more frequent than high ids
    low = (toks < 16).mean()
    high = (toks >= 128).mean()
    assert low > 5 * high, (low, high)


def test_prefetch_loader_orders_steps():
    loader = PrefetchLoader(lambda s: s * 10, start_step=3, depth=2)
    steps = [next(loader) for _ in range(4)]
    loader.close()
    assert steps == [(3, 30), (4, 40), (5, 50), (6, 60)]


def test_modality_batches():
    import dataclasses

    vlm = dataclasses.replace(CFG, family="vlm", n_patches=8)
    b = lm_batch(vlm, 0, 0, 2, 16)
    assert b["patches"].shape == (2, 8, 64)
    audio = dataclasses.replace(CFG, family="audio", n_enc_layers=2,
                                enc_frames=12)
    b = lm_batch(audio, 0, 0, 2, 16)
    assert b["frames"].shape == (2, 12, 64)


# -- density weighting (the paper's technique as a data feature) -----------------


def test_density_weights_upweight_tails():
    key = jax.random.PRNGKey(0)
    dense = jax.random.normal(key, (400, 4)) * 0.1        # tight cluster
    sparse = jax.random.normal(jax.random.fold_in(key, 1), (40, 4)) * 3 + 5
    emb = jnp.concatenate([dense, sparse])
    w = density_weights(emb, alpha=0.5)
    assert float(w[400:].mean()) > 2.0 * float(w[:400].mean())
    assert abs(float(w.mean()) - 1.0) < 1e-3


def test_density_weighting_pipeline_stage():
    key = jax.random.PRNGKey(1)
    corpus = jax.random.normal(key, (500, 8))
    stage = DensityWeighting(alpha=0.5).fit(corpus)
    batch = jax.random.normal(jax.random.fold_in(key, 2), (64, 8))
    w = stage(batch)
    assert w.shape == (64,) and np.isfinite(np.asarray(w)).all()
    idx = stage.resample_indices(batch, jax.random.PRNGKey(3), 16)
    assert idx.shape == (16,) and len(set(np.asarray(idx).tolist())) == 16


# -- estimator API -----------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_estimator_backends_agree(backend):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (200, 8))
    y = jax.random.normal(jax.random.fold_in(key, 1), (50, 8))
    cfg = EstimatorConfig(backend=backend, block_m=32, block_n=64,
                          interpret=True)
    ref_cfg = EstimatorConfig(backend="jnp")
    for cls in (KDE, SDKDE, LaplaceKDE):
        a = cls(0.5, cfg).fit(x).evaluate(y)
        b = cls(0.5, ref_cfg).fit(x).evaluate(y)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4)


def test_estimator_auto_bandwidth():
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 4))
    est = SDKDE().fit(x)
    assert est.h is not None and float(est.h) > 0
