"""All three MoE dispatch modes agree with the dense reference.

  * train shard-local (tokens stay in their data shards, local capacity)
  * decode weights-stationary (tokens replicated, weights never move)
  * dense fallback (no mesh — smoke-test path)

Covers EP (experts over model), TPE (d_ff over model, expert count
indivisible) and the 2-D kimi layout (EP + d_ff over data, FSDP gather in
train / pure-partial in decode), with shared experts.
"""

import os
import subprocess
import sys

import pytest

_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 2, 2), ('pod', 'data', 'model'))
from repro.models.common import ModelConfig, init_params
from repro.models import moe

for ename, (E, e2d) in {'tpe': (5, False), 'ep': (8, False),
                        'ep2d': (8, True)}.items():
    cfg = ModelConfig(name='m', family='moe', n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16, n_experts=E, top_k=2, moe_dff=32,
                      n_shared_experts=1, capacity_factor=8.0,
                      expert_2d_sharding=e2d, dtype=jnp.float32,
                      remat='none', loss_chunk=0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = {k.split('/', 1)[1]: v[0] for k, v in params.items()
          if k.startswith('layers/')}
    lp = {k: v for k, v in lp.items() if k in moe._MOE_WEIGHTS}

    # decode-scale: stationary path
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    moe.set_moe_mesh(None)
    ref, _ = moe._moe_ffn_body(x, lp, cfg)
    moe.set_moe_mesh(mesh)
    st, _ = jax.jit(lambda a, w: moe.moe_ffn(a, w, cfg))(x, lp)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ref),
                               rtol=3e-4, atol=2e-5)

    # train-scale: shard-local path (per-shard capacity == dense at equal
    # per-shard token count; no drops at cf=8)
    xt = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (4096, 64)),
        NamedSharding(mesh, P(('pod', 'data'), None)))
    moe.set_moe_mesh(None)
    ref2, _ = moe._moe_ffn_body(np.asarray(xt)[:1024], lp, cfg)
    moe.set_moe_mesh(mesh)
    sh, _ = jax.jit(lambda a, w: moe.moe_ffn(a, w, cfg))(xt, lp)
    np.testing.assert_allclose(np.asarray(sh)[:1024], np.asarray(ref2),
                               rtol=3e-4, atol=2e-5)

    # gradients flow through both sharded paths
    jax.jit(jax.grad(lambda w, a: moe.moe_ffn(a, w, cfg)[0].sum()))(lp, xt)
    print(f'{ename} OK')
moe.set_moe_mesh(None)
print('ALL_OK')
"""


@pytest.mark.slow
def test_all_dispatch_modes_agree():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=1800,
    )
    assert "ALL_OK" in out.stdout, out.stdout[-1500:] + out.stderr[-2500:]
