"""Serving quickstart: one debias pass, many cheap query batches.

    PYTHONPATH=src python examples/serve_queries.py
"""

import jax

from repro.core.mixtures import mixture_for_dim
from repro.serve import QueryRequest, ServeConfig, ServeEngine


def main():
    mix = mixture_for_dim(8)
    key = jax.random.PRNGKey(0)

    # One engine can serve many datasets; each is debiased exactly once.
    eng = ServeEngine(ServeConfig(backend="pallas", method="sdkde",
                                  block_m=32, block_n=256,
                                  min_batch=32, max_batch=256))
    eng.register("tenant-a", mix.sample(key, 2048))
    eng.register("tenant-b", mix.sample(jax.random.fold_in(key, 1), 1024))
    print(f"registered {eng.registry.keys()}  "
          f"(debias passes run: {eng.registry.n_fits})")

    # Ragged single requests: padded to shape buckets, no recompile storms.
    pool = mix.sample(jax.random.fold_in(key, 2), 512)
    for m in (3, 40, 170, 40, 3):
        dens = eng.query(QueryRequest(key="tenant-a",
                                      points=pool[:m])).value
        print(f"query m={m:4d} -> bucket exec, density[0]={float(dens[0]):.3e}")

    # Micro-batching: coalesce concurrent requests into ONE dispatch.
    outs = [a.value for a in eng.query_many(
        [QueryRequest(key="tenant-b", points=q)
         for q in (pool[:5], pool[5:90], pool[90:101])])]
    print(f"coalesced 3 requests -> shapes {[tuple(o.shape) for o in outs]}")

    s = eng.latency.summary()
    print(f"latency: p50={s.p50_ms:.2f}ms p99={s.p99_ms:.2f}ms "
          f"({s.queries} queries)")
    print(f"bucket cache: {eng.cache.hits} hits / {eng.cache.misses} misses")

    # Re-registering is free — the registry is idempotent per key.
    eng.register("tenant-a", mix.sample(key, 2048))
    print(f"after re-register: debias passes still {eng.registry.n_fits}")


if __name__ == "__main__":
    main()
