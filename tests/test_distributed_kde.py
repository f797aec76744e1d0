"""Ring / ring2d distributed SD-KDE == single-device reference.

Runs on 8 forced host devices (subprocess-free: this file is executed by
pytest in the main process, so we spawn a child python with XLA_FLAGS —
the main test process must keep seeing ONE device for the smoke tests).
"""

import json
import os
import subprocess
import sys

import pytest

_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import kde as ref
from repro.distributed import ring
from repro.distributed.ring2d import ring2d_sdkde, ring2d_kde_sums

def make_mesh(shape, axes):
    try:  # jax >= 0.5: explicit axis types
        from jax.sharding import AxisType
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    except ImportError:
        import numpy as np
        from jax.sharding import Mesh
        n = int(np.prod(shape))
        return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)

x = jax.random.normal(jax.random.PRNGKey(0), (256, 8))
y = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
h = 0.6
p_ref = np.asarray(ref.sdkde_eval(x, y, h, block=64))

mesh2 = make_mesh((4, 2), ('data', 'model'))
mesh3 = make_mesh((2, 2, 2), ('pod', 'data', 'model'))

# the hierarchical (pod, data) ring behind ServeEngine's ring evaluation
p = np.asarray(ring.ring_kde(x, y, h, mesh=mesh3, pod_axis='pod'))
np.testing.assert_allclose(p, np.asarray(ref.kde_eval(x, y, h, block=64)),
                           rtol=2e-4)

p = np.asarray(ring2d_sdkde(x, y, h, mesh=mesh2, chunk=32))
np.testing.assert_allclose(p, p_ref, rtol=2e-4)

p = np.asarray(ring2d_sdkde(x, y, h, mesh=mesh3, chunk=32))
np.testing.assert_allclose(p, p_ref, rtol=2e-4)

# laplace variant on the ring
p_lc_ref = np.asarray(ref.laplace_kde_eval(x, y, h, block=64))
s = np.asarray(ring2d_kde_sums(y, x, h, mesh=mesh2, chunk=32, laplace=True))
from repro.core.bandwidth import gaussian_norm_const
p_lc = s / (256 * gaussian_norm_const(8, 1.0) * h**8)
np.testing.assert_allclose(p_lc, p_lc_ref, rtol=2e-4)

# ring KDE with explicit n_true (padding correctness)
xs = ring.shard_points(x[:200], mesh2, ('data',))
p_pad = np.asarray(ring.ring_kde(xs, y, h, n_true=200, mesh=mesh2))
p_pad_ref = np.asarray(ref.kde_eval(x[:200], y, h, block=64))
np.testing.assert_allclose(p_pad, p_pad_ref, rtol=2e-4)
print('ALL_OK')
"""


@pytest.mark.slow
def test_ring_variants_match_reference():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert "ALL_OK" in out.stdout, out.stdout + out.stderr
