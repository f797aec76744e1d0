"""Cell-builder compile tests: every family × shape kind on a mini mesh.

The full production meshes are exercised by launch/dryrun.py; this locks
the same code paths into the test suite at 8 forced host devices with
reduced configs (subprocess, so the main process keeps one device).
"""

import os
import subprocess
import sys

import pytest

_CHILD = r"""
import jax, jax.numpy as jnp, dataclasses
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 2, 2), ('pod', 'data', 'model'))
from repro.configs import get_arch, ShapeCfg
from repro.launch.steps import build_cell

ARCHS = ['gemma2_2b', 'kimi_k2_1t_a32b', 'granite_moe_3b_a800m',
         'falcon_mamba_7b', 'hymba_1p5b', 'llava_next_34b',
         'whisper_large_v3']
SHAPES = [ShapeCfg('train', 'train', 128, 16, microbatches=2),
          ShapeCfg('prefill', 'prefill', 256, 8),
          ShapeCfg('decode', 'decode', 256, 8),
          ShapeCfg('long', 'decode', 1024, 1)]
for arch_id in ARCHS:
    arch = get_arch(arch_id)
    small = arch.model.reduced(dtype=jnp.bfloat16, remat='full',
                               loss_chunk=64)
    arch = dataclasses.replace(arch, model=small, train_microbatches=None)
    for shape in SHAPES:
        fn, abstract, donate = build_cell(arch, shape, mesh)
        jax.jit(fn, donate_argnums=donate).lower(*abstract).compile()
        print(f'{arch_id}/{shape.name} OK')
print('ALL_OK')
"""


@pytest.mark.slow
def test_all_cell_kinds_compile_on_mini_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=3000,
    )
    assert "ALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
