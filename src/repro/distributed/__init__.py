"""Multi-chip SD-KDE: the Flash kernels sharded over every local device
(``shard``), the XLA rings (``ring``, ``ring2d``), and the replica
supervisor and mesh planning behind the resilient engine (``fault``,
``elastic``).  Import the submodule you need; this package re-exports
nothing."""
