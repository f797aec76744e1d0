"""Multi-device SD-KDE: the 2-D ring decomposition on a host-device mesh.

Runs ``ring2d_sdkde`` (score rows over ``model``, train columns over
(pod, data)) on 8 forced host devices, and checks it against the
single-device reference — the scaled-down multi-pod demonstration.

    PYTHONPATH=src python examples/distributed_sdkde.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import kde as ref  # noqa: E402
from repro.distributed.ring2d import pad_for_mesh, ring2d_sdkde  # noqa: E402
from repro.core.mixtures import benchmark_mixture_16d  # noqa: E402


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices")

    mix = benchmark_mixture_16d()
    key = jax.random.PRNGKey(0)
    x = mix.sample(key, 16384)
    y = mix.sample(jax.random.fold_in(key, 1), 2048)
    h = 0.5

    x = pad_for_mesh(x, mesh)
    fn = jax.jit(lambda a, b: ring2d_sdkde(a, b, h, mesh=mesh, chunk=512))
    t0 = time.time()
    p = np.asarray(fn(x, y))
    t_ring = time.time() - t0

    p_ref = np.asarray(ref.sdkde_eval(x, y, h, block=2048))
    np.testing.assert_allclose(p, p_ref, rtol=3e-4)
    print(f"ring2d SD-KDE on 16k points x 2k queries: {t_ring*1e3:.0f}ms "
          f"(incl. compile), max rel err "
          f"{float(np.max(np.abs(p - p_ref) / np.abs(p_ref))):.2e}")
    print("distributed == single-device: OK")


if __name__ == "__main__":
    main()
