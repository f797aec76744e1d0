"""Top-k MoE layer with capacity-based dispatch (GShard/Switch style).

Routing: softmax router → top-k experts per token → position-in-expert via
cumulative sum → tokens beyond an expert's capacity are dropped (their
residual passes through).  Dispatch/combine are scatter/gather ops; the
expert FFNs run as a single batched GEMM over the (E, C, d) buffer, which
shards cleanly:

  * EP  — expert axis over ``model`` (used when n_experts % 16 == 0, e.g.
          kimi-k2's 384 experts → 24/device).
  * TPE — per-expert d_ff over ``model`` (granite's 40 experts don't divide
          the axis; its d_ff=512 does).

**Shard-local dispatch** (the §Perf fix; see EXPERIMENTS.md): under pure
GSPMD the scatter-based dispatch builds a GLOBAL (E, C, d) capacity buffer
(C ∝ the full microbatch) that the partitioner replicates and all-reduces
per layer — the dominant collective cost of both assigned MoE cells
(7.5 GiB payloads × layers × microbatches for granite).  When a mesh is
registered via ``set_moe_mesh``, the dispatch/combine run inside a
partial-manual ``shard_map`` over the batch axes: every data shard routes
its OWN tokens into a LOCAL buffer (C_loc ∝ T/dp), while the expert weights
stay auto-sharded over ``model`` — the only cross-device traffic left is
the model-axis reduction GSPMD inserts for the expert GEMMs.  kimi-scale
2-D expert sharding (d_ff over ``data``) additionally all-gathers the
CURRENT layer's expert weights over ``data`` inside the manual region
(FSDP-style transient gather, freed after the layer).

Aux losses: load-balancing (Switch) + router z-loss, pmean'd over shards.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.layers import _act

# Trace-time mesh registry (shared with the attention hints): step builders
# register the mesh so the MoE layer can open a fully-manual region.  None
# (the default) keeps the pure-GSPMD dense path — used by single-device
# smoke tests and kept as the §Perf BASELINE.
from repro.models.parallel import dp_axes as _dp_axes  # noqa: E402
from repro.models.parallel import get_mesh as _get_mesh  # noqa: E402
from repro.models.parallel import model_mesh as moe_mesh  # noqa: F401,E402
from repro.models.parallel import set_mesh as set_moe_mesh  # noqa: F401,E402


def _top_k_routing(logits: jnp.ndarray, k: int):
    """Return (weights, expert_idx): renormalized top-k softmax routing."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_e


def moe_ffn(
    x: jnp.ndarray,          # (T, d) flattened tokens
    lp: dict,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply the MoE FFN; returns (output (T, d), aux_loss scalar).

    Dispatches to the shard-local path when a mesh with >1 batch shard is
    registered (see module docstring), else the dense GSPMD path.
    """
    mesh = _get_mesh()
    dp = _dp_axes(mesh)
    if mesh is not None and x.shape[0] <= 2048:
        # Decode-scale batches: weights-STATIONARY path.  Moving 2 TB of
        # experts for 128 tokens is absurd (GSPMD's auto choice gathered
        # one full layer = 34 GB/device on kimi decode); instead replicate
        # the tiny token batch, compute each shard's (E_loc × f_loc)
        # partial, and psum the (T, d) output — ~0.5 MB per layer.
        return _moe_ffn_stationary(x, lp, cfg, mesh)
    if dp and x.shape[0] % _dp_size(mesh, dp) == 0:
        return _moe_ffn_sharded(x, lp, cfg, mesh, dp)
    return _moe_ffn_body(x, lp, cfg)


def _dp_size(mesh, dp) -> int:
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    return n


_MOE_WEIGHTS = ("router", "experts_up", "experts_gate", "experts_down",
                "shared_up", "shared_gate", "shared_down")


def _moe_weight_specs(cfg: ModelConfig, names):
    """The stored param PartitionSpecs (models.common._moe_shapes) — the
    manual region consumes weights exactly as they live in HBM."""
    from repro.models.common import _moe_shapes

    shapes = _moe_shapes(cfg)
    return {n: shapes[n][2] for n in names}


def _moe_ffn_sharded(x, lp, cfg: ModelConfig, mesh, dp):
    """Fully-manual dispatch: manual over (pod, data) AND model.

    Every shard routes its OWN T/dp tokens (local capacity, local scatter).
    Expert parallelism without all-to-all: with E sharded over ``model``,
    each model shard buffers only its E/16 experts (out-of-range routes are
    masked); with d_ff sharded over ``model`` (granite) each shard computes
    an f-slice partial.  Either way the final combine is ONE f32 psum of
    the (T_loc, d) layer output over ``model`` — the minimal collective the
    math admits.  kimi's 2-D expert sharding first all-gathers the current
    layer's d_ff slices over ``data`` (transient FSDP gather).
    """
    weights = {k: v for k, v in lp.items() if k in _MOE_WEIGHTS}
    wspecs = _moe_weight_specs(cfg, weights)
    manual = set(dp) | {"model"}
    ep = cfg.n_experts % mesh.shape["model"] == 0

    def local(x_loc, w_loc):
        if cfg.expert_2d_sharding and "data" in dp:
            w_loc = dict(w_loc)
            for name, axis in (("experts_up", 2), ("experts_gate", 2),
                               ("experts_down", 1)):
                if name in w_loc:
                    # optimization_barrier: stops XLA from hoisting the
                    # einsum's bf16→f32 convert ABOVE this gather, which
                    # would double the wire bytes (measured §Perf kimi#2).
                    w_loc[name] = lax.optimization_barrier(
                        lax.all_gather(
                            w_loc[name], "data", axis=axis, tiled=True
                        )
                    )
        out, aux = _moe_ffn_manual(x_loc, w_loc, cfg, ep=ep)
        return out, lax.pmean(aux, tuple(manual))

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None), wspecs),
        out_specs=(P(dp, None), P()),
        axis_names=manual,
        check_vma=False,
    )(x, weights)


def _moe_ffn_stationary(x, lp, cfg: ModelConfig, mesh):
    """Weights-stationary MoE for small (decode) batches.

    Manual over every mesh axis; tokens replicated (in_specs P(None));
    expert weights stay exactly where they live (native param specs —
    including kimi's 2-D (model, data) layout, NO gather); each device
    computes its experts'/f-slice partial for all T tokens; the final psum
    over ALL axes merges expert locality and f partials at once.
    """
    weights = {k: v for k, v in lp.items() if k in _MOE_WEIGHTS}
    wspecs = _moe_weight_specs(cfg, weights)
    axes = tuple(mesh.axis_names)
    ep = cfg.n_experts % mesh.shape["model"] == 0
    # Reduce ONLY over axes the weights are sharded on: partials exist
    # over 'model' (experts or f) and — for 2-D expert layouts — 'data'
    # (f slices); over any other axis the compute is replicated and a
    # psum would overcount it.
    reduce_axes = ("model",) + (
        ("data",) if cfg.expert_2d_sharding and "data" in axes else ()
    )

    def local(x_loc, w_loc):
        out, aux = _moe_ffn_manual(x_loc, w_loc, cfg, ep=ep,
                                   psum_axes=reduce_axes)
        return out, lax.pmean(aux, axes)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), wspecs),
        out_specs=(P(None, None), P()),
        axis_names=set(axes),
        check_vma=False,
    )(x, weights)


def _moe_ffn_manual(x, lp, cfg: ModelConfig, *, ep: bool, psum_axes=None):
    """Per-device MoE body inside the fully-manual region.

    ``ep=True``: lp['experts_*'] hold this model shard's E_loc experts.
    ``ep=False``: all experts present, d_ff arrives f-sliced (TP-in-expert).
    Returns the (T_loc, d) output AFTER the model-axis psum.
    """
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    mp = lax.axis_size("model")
    e_loc = lp["experts_up"].shape[0]

    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    weights, expert_idx = _top_k_routing(logits, k)           # (T, k)

    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(onehot.mean(0) * probs.mean(0)) + 1e-3 * jnp.mean(
        jnp.log(jnp.sum(jnp.exp(logits), axis=-1)) ** 2
    )

    # Capacity bookkeeping over the FULL expert range (identical across
    # model shards, and to the dense path at equal per-shard token count).
    cap = int(cfg.capacity_factor * t * k / e) + 1
    flat_e = expert_idx.reshape(-1)
    onehot_te = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot_te, axis=0) - 1
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = slot < cap
    if ep and e_loc < e:
        shard = lax.axis_index("model")
        local_e = flat_e - shard * e_loc
        keep = keep & (local_e >= 0) & (local_e < e_loc)
    else:
        local_e = flat_e
    dest = jnp.where(keep, local_e * cap + slot, e_loc * cap)

    xk = jnp.repeat(x, k, axis=0)
    buf = jnp.zeros((e_loc * cap + 1, d), x.dtype).at[dest].set(
        jnp.where(keep[:, None], xk, 0)
    )
    buf = buf[: e_loc * cap].reshape(e_loc, cap, d)

    # Expert GEMMs consume weights in their STORAGE dtype (bf16) with f32
    # MXU accumulation — upcasting the operands would double both HBM and
    # (for 2-D-sharded experts) all-gather traffic.
    up = jnp.einsum("ecd,edf->ecf", buf, lp["experts_up"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.gated:
        gate = jnp.einsum("ecd,edf->ecf", buf, lp["experts_gate"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
        h = _act(gate, cfg.act) * up
    else:
        h = _act(up, cfg.act)
    out_buf = jnp.einsum("ecf,efd->ecd", h, lp["experts_down"],
                         preferred_element_type=jnp.float32).astype(x.dtype)

    out_flat = out_buf.reshape(e_loc * cap, d)
    gathered = jnp.where(
        keep[:, None], out_flat[jnp.minimum(dest, e_loc * cap - 1)], 0.0
    )
    out = (
        gathered.reshape(t, k, d) * weights[..., None].astype(x.dtype)
    ).sum(axis=1)

    if cfg.n_shared_experts:
        s_up = x @ lp["shared_up"].astype(x.dtype)
        if cfg.gated:
            s_h = _act(x @ lp["shared_gate"].astype(x.dtype), cfg.act) * s_up
        else:
            s_h = _act(s_up, cfg.act)
        shared = s_h @ lp["shared_down"].astype(x.dtype)
        # The shared expert is sharded over 'model' ONLY; when the combine
        # psum also spans 'data' (stationary path, 2-D experts), its
        # data-replicated partial would be overcounted — pre-scale by the
        # extra reduction factor (a power of two: exact in fp).
        axes = psum_axes if psum_axes is not None else ("model",)
        extra = 1
        for a in axes:
            if a != "model":
                extra *= lax.axis_size(a)
        out = out + (shared / extra if extra > 1 else shared)

    # ONE combine psum: merges EP expert-locality masking and/or f-slice
    # partial sums (and the f-sliced shared expert) in a single collective.
    # The stationary (decode) path reduces over every weight-sharded axis.
    axes = psum_axes if psum_axes is not None else ("model",)
    if any(lax.axis_size(a) > 1 for a in axes):
        out = lax.psum(out.astype(jnp.float32), axes).astype(x.dtype)
    return out, aux


def _moe_ffn_body(
    x: jnp.ndarray,          # (T, d) flattened tokens (global or per-shard)
    lp: dict,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(cfg.capacity_factor * t * k / e) + 1

    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    weights, expert_idx = _top_k_routing(logits, k)           # (T,k)

    # Load-balance loss (Switch): E * Σ_e f_e · p_e ; z-loss on router logits.
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    f = onehot.mean(0)
    p = probs.mean(0)
    aux = e * jnp.sum(f * p) + 1e-3 * jnp.mean(
        jnp.log(jnp.sum(jnp.exp(logits), axis=-1)) ** 2
    )

    # Position of each (token, choice) within its expert's capacity buffer.
    flat_e = expert_idx.reshape(-1)                           # (T*k,)
    onehot_te = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # (T*k, E)
    pos_in_e = jnp.cumsum(onehot_te, axis=0) - 1              # (T*k, E)
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = slot < cap
    dest = jnp.where(keep, flat_e * cap + slot, e * cap)      # drop bucket

    # Dispatch: scatter token vectors into the (E*C+1, d) buffer.
    xk = jnp.repeat(x, k, axis=0)                             # (T*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xk)
    buf = buf[: e * cap].reshape(e, cap, d)

    # Expert FFNs as batched GEMMs over the expert axis.
    up = jnp.einsum("ecd,edf->ecf", buf, lp["experts_up"].astype(x.dtype))
    if cfg.gated:
        gate = jnp.einsum(
            "ecd,edf->ecf", buf, lp["experts_gate"].astype(x.dtype)
        )
        h = _act(gate, cfg.act) * up
    else:
        h = _act(up, cfg.act)
    out_buf = jnp.einsum("ecf,efd->ecd", h, lp["experts_down"].astype(x.dtype))

    # Combine: gather each (token, choice) back and weight.
    out_flat = out_buf.reshape(e * cap, d)
    gathered = jnp.where(
        keep[:, None], out_flat[jnp.minimum(dest, e * cap - 1)], 0.0
    )
    out = (
        gathered.reshape(t, k, d)
        * weights[..., None].astype(x.dtype)
    ).sum(axis=1)

    # Shared experts (kimi-k2 style): always-on dense FFN on the side.
    if cfg.n_shared_experts:
        s_up = x @ lp["shared_up"].astype(x.dtype)
        if cfg.gated:
            s_gate = _act(x @ lp["shared_gate"].astype(x.dtype), cfg.act)
            s_h = s_gate * s_up
        else:
            s_h = _act(s_up, cfg.act)
        out = out + s_h @ lp["shared_down"].astype(x.dtype)

    return out.astype(x.dtype), aux
