"""Quickstart: fit and evaluate Flash-SD-KDE in ten lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core.estimator import SDKDE, KDE, LaplaceKDE, EstimatorConfig
from repro.core.metrics import oracle_errors
from repro.core.mixtures import benchmark_mixture_16d


def main():
    mix = benchmark_mixture_16d()
    key = jax.random.PRNGKey(0)
    x_train = mix.sample(key, 8192)                    # 16-D mixture samples
    x_query = mix.sample(jax.random.fold_in(key, 1), 1024)

    # --- the paper's estimator, default (streaming-GEMM) backend ---------
    sdkde = SDKDE().fit(x_train)                       # score pass + shift
    density = sdkde.evaluate(x_query)                  # KDE on debiased pts
    print(f"SD-KDE: h={float(sdkde.h):.4f}  "
          f"density[:4]={[f'{v:.3e}' for v in density[:4]]}")

    # --- same API, Pallas kernel backend (interpreted on CPU) -----------
    flash = SDKDE(config=EstimatorConfig(backend="pallas", block_m=128,
                                         block_n=512)).fit(x_train[:2048])
    print(f"Pallas backend density[0]={float(flash(x_query[:8])[0]):.3e}")

    # --- accuracy vs the oracle: SD-KDE beats classical KDE --------------
    h = float(sdkde.h)
    for name, est in [("kde", KDE(h)), ("sdkde", SDKDE(h)),
                      ("laplace", LaplaceKDE(h))]:
        est.fit(x_train)
        e = oracle_errors(lambda g: est.evaluate(g), mix, key, n_mc=2048)
        print(f"{name:8s} MISE={e.mise:.3e} MIAE={e.miae:.3e} "
              f"neg_mass={e.neg_mass:.2e}")


if __name__ == "__main__":
    main()
