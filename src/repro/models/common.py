"""Model substrate: configuration, parameter trees, and sharding rules.

One ``ModelConfig`` dataclass covers all ten assigned architecture families
(dense / MoE / SSM / hybrid / VLM / audio enc-dec).  A single source of truth
— ``param_shapes(cfg)`` — defines every parameter's shape, dtype and logical
PartitionSpec; ``init_params`` materializes it for smoke tests / real
training and ``abstract_params`` produces sharded ShapeDtypeStructs for the
multi-pod dry-run (no allocation).

Sharding rules (Megatron-style TP over the ``model`` axis):
  * embeddings / lm_head: vocab-sharded over ``model``
  * attention qkv: output-feature sharded; wo: input-feature sharded
  * MLP: d_ff sharded (column- then row-parallel)
  * MoE: expert axis sharded over ``model`` when divisible (EP), else the
    per-expert d_ff axis (TP-in-expert)
  * SSM: d_inner sharded
  * norms / small vectors: replicated
Batch (and sequence for long-context decode caches) shards over ``data``
(+``pod``).  Head counts that don't divide the 16-way ``model`` axis rely on
GSPMD padding — the projection matrices shard on the flattened head*dim
feature axis, which is 128-aligned for every assigned config.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Literal, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // n_heads
    act: str = "swiglu"                # swiglu | geglu | gelu | relu2
    rms_one_plus: bool = False         # gemma-style (1 + w) RMSNorm scale
    post_norms: bool = False           # gemma2 sandwich norms
    rope_variant: str = "full"         # full | half (chatglm 2d rope)
    rope_theta: float = 10000.0
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    local_global_alt: bool = False     # gemma2 alternating local/global
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_dff: int = 0                   # per-expert FFN width
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # 2-D expert sharding (kimi-scale): expert axis over ``model`` AND the
    # per-expert d_ff over ``data`` — required to fit ~1T bf16 params/pod.
    expert_2d_sharding: bool = False
    # SSM (mamba1)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    attn_free: bool = False            # falcon-mamba: no attention at all
    # enc-dec (whisper) — frontend is a stub; encoder sees frame embeddings
    n_enc_layers: int = 0
    enc_frames: int = 1500
    # VLM (llava) — patch frontend is a stub
    n_patches: int = 0
    # numerics
    dtype: Any = jnp.bfloat16          # activations
    param_dtype: Any = jnp.float32
    remat: str = "full"                # none | full
    loss_chunk: int = 512              # sequence chunking for the vocab loss
    # sequence-sharded attention hint: None = auto (hint only when n_heads
    # doesn't divide the model axis); measured per-arch overrides in §Perf.
    seq_shard_attn: Optional[bool] = None
    # int8 KV cache (serving): halves the decode memory term; per-position
    # per-head symmetric scales, dequant fused into the attention reads.
    kv_quant: bool = False
    # Mamba path: use the chunked Pallas selective-scan kernel
    # (kernels/selective_scan.py) instead of the XLA associative scan.
    # interpreted on CPU (validation); compiled on TPU.
    ssm_kernel: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to 256 — 16-way TP divisibility + lane alignment.

        Standard production practice (MaxText, Megatron): the embedding /
        lm_head vocab axis is padded so it shards evenly; padded ids are
        never produced by the tokenizer and their logits are free to float.
        """
        return -(-self.vocab_size // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, math.ceil(self.d_model / 16))

    @property
    def gated(self) -> bool:
        return self.act in ("swiglu", "geglu")

    def reduced(self, **overrides) -> "ModelConfig":
        """Small same-family variant for CPU smoke tests."""
        shrink = dict(
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            d_ff=128,
            vocab_size=256,
            head_dim=16,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_dff=32 if self.n_experts else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            sliding_window=8 if self.sliding_window else None,
            n_enc_layers=2 if self.n_enc_layers else 0,
            enc_frames=16 if self.n_enc_layers else 1500,
            n_patches=8 if self.n_patches else 0,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            remat="none",
            loss_chunk=0,
        )
        shrink.update(overrides)
        return dataclasses.replace(self, **shrink)


ShapeSpec = Tuple[Tuple[int, ...], Any, P]  # (shape, dtype, pspec)


def _stack(layer_shapes: Dict[str, ShapeSpec], n_layers: int,
           prefix: str) -> Dict[str, ShapeSpec]:
    """Prepend the stacked-layer axis (scan-over-layers layout)."""
    out = {}
    for k, (shape, dt, spec) in layer_shapes.items():
        out[f"{prefix}{k}"] = ((n_layers, *shape), dt, P(None, *spec))
    return out


def _attn_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    d, pd = cfg.d_model, cfg.param_dtype
    return {
        "attn_norm": ((d,), pd, P(None)),
        "wq": ((d, cfg.q_dim), pd, P(None, "model")),
        "wk": ((d, cfg.kv_dim), pd, P(None, "model")),
        "wv": ((d, cfg.kv_dim), pd, P(None, "model")),
        "wo": ((cfg.q_dim, d), pd, P("model", None)),
    }


def _mlp_shapes(cfg: ModelConfig, d_ff: int) -> Dict[str, ShapeSpec]:
    d, pd = cfg.d_model, cfg.param_dtype
    out: Dict[str, ShapeSpec] = {
        "mlp_norm": ((d,), pd, P(None)),
        "w_up": ((d, d_ff), pd, P(None, "model")),
        "w_down": ((d_ff, d), pd, P("model", None)),
    }
    if cfg.gated:
        out["w_gate"] = ((d, d_ff), pd, P(None, "model"))
    return out


def _moe_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    d, pd, e, f = cfg.d_model, cfg.param_dtype, cfg.n_experts, cfg.moe_dff
    # EP if the expert count divides the model axis cleanly; else shard d_ff.
    ep = (e % 16 == 0)
    if cfg.expert_2d_sharding:
        # kimi-scale: experts over ``model``, per-expert d_ff over ``data``.
        es = ("model", None, "data")
        es_down = ("model", "data", None)
    elif ep:
        es = es_down = ("model", None, None)
    else:
        es = (None, None, "model")
        es_down = (None, "model", None)
    out: Dict[str, ShapeSpec] = {
        "mlp_norm": ((d,), pd, P(None)),
        "router": ((d, e), pd, P(None, None)),
        "experts_up": ((e, d, f), pd, P(*es)),
        "experts_down": ((e, f, d), pd, P(*es_down)),
    }
    if cfg.gated:
        out["experts_gate"] = ((e, d, f), pd, P(*es))
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        out["shared_up"] = ((d, fs), pd, P(None, "model"))
        out["shared_down"] = ((fs, d), pd, P("model", None))
        if cfg.gated:
            out["shared_gate"] = ((d, fs), pd, P(None, "model"))
    return out


def _ssm_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    d, pd = cfg.d_model, cfg.param_dtype
    di, n, dtr, dc = cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    return {
        "ssm_norm": ((d,), pd, P(None)),
        "in_proj": ((d, 2 * di), pd, P(None, "model")),
        "conv_w": ((dc, di), pd, P(None, "model")),
        "conv_b": ((di,), pd, P("model")),
        "x_proj": ((di, dtr + 2 * n), pd, P("model", None)),
        "dt_proj": ((dtr, di), pd, P(None, "model")),
        "dt_bias": ((di,), pd, P("model")),
        "A_log": ((di, n), pd, P("model", None)),
        "D": ((di,), pd, P("model")),
        "out_proj": ((di, d), pd, P("model", None)),
    }


def _layer_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    d, pd = cfg.d_model, cfg.param_dtype
    shapes: Dict[str, ShapeSpec] = {}
    if cfg.family == "ssm":
        shapes.update(_ssm_shapes(cfg))
        return shapes
    if cfg.family == "hybrid":
        shapes.update(_attn_shapes(cfg))
        shapes.update(_ssm_shapes(cfg))
        shapes["fuse_attn_scale"] = ((d,), pd, P(None))
        shapes["fuse_ssm_scale"] = ((d,), pd, P(None))
        shapes.update(_mlp_shapes(cfg, cfg.d_ff))
        return shapes
    shapes.update(_attn_shapes(cfg))
    if cfg.family == "moe":
        shapes.update(_moe_shapes(cfg))
    else:
        shapes.update(_mlp_shapes(cfg, cfg.d_ff))
    if cfg.post_norms:
        shapes["post_attn_norm"] = ((d,), pd, P(None))
        shapes["post_mlp_norm"] = ((d,), pd, P(None))
    return shapes


def _enc_layer_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    """Whisper encoder layer: bidirectional attention + gelu MLP."""
    shapes = dict(_attn_shapes(cfg))
    shapes.update(_mlp_shapes(cfg, cfg.d_ff))
    return shapes


def _dec_cross_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    d, pd = cfg.d_model, cfg.param_dtype
    return {
        "xattn_norm": ((d,), pd, P(None)),
        "xwq": ((d, cfg.q_dim), pd, P(None, "model")),
        "xwk": ((d, cfg.kv_dim), pd, P(None, "model")),
        "xwv": ((d, cfg.kv_dim), pd, P(None, "model")),
        "xwo": ((cfg.q_dim, d), pd, P("model", None)),
    }


def param_shapes(cfg: ModelConfig) -> Dict[str, ShapeSpec]:
    """Flat dict path -> (shape, dtype, PartitionSpec) — the single source
    of truth for init, abstract specs and sharding."""
    d, v, pd = cfg.d_model, cfg.padded_vocab, cfg.param_dtype
    shapes: Dict[str, ShapeSpec] = {
        "embed": ((v, d), pd, P("model", None)),
        "final_norm": ((d,), pd, P(None)),
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = ((d, v), pd, P(None, "model"))
    shapes.update(_stack(_layer_shapes(cfg), cfg.n_layers, "layers/"))
    if cfg.family == "audio":
        # Encoder stack + cross-attention in the decoder. Conv frontend is a
        # stub: the encoder consumes precomputed frame embeddings.
        shapes["enc_pos"] = ((cfg.enc_frames, d), pd, P(None, None))
        shapes["enc_final_norm"] = ((d,), pd, P(None))
        shapes.update(
            _stack(_enc_layer_shapes(cfg), cfg.n_enc_layers, "enc_layers/")
        )
        shapes.update(
            _stack(_dec_cross_shapes(cfg), cfg.n_layers, "layers/")
        )
    if cfg.family == "vlm":
        # Patch frontend is a stub: a single learned projection applied to
        # precomputed patch embeddings.
        shapes["patch_proj"] = ((d, d), pd, P(None, "model"))
    return shapes


def param_count(cfg: ModelConfig) -> int:
    return sum(int(np.prod(s)) for s, _, _ in param_shapes(cfg).values())


def active_param_count(cfg: ModelConfig) -> int:
    """Activated parameters per token (MoE: top_k of n_experts)."""
    if cfg.family != "moe" or not cfg.n_experts:
        return param_count(cfg)
    total = 0
    for name, (shape, _, _) in param_shapes(cfg).items():
        n = int(np.prod(shape))
        if "experts_" in name:
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total


def _init_one(key, name: str, shape, dtype):
    if not shape or shape[-1] == 0:
        return jnp.zeros(shape, dtype)
    last = name.split("/")[-1]
    if "norm" in last or last in ("conv_b", "dt_bias", "D"):
        return jnp.ones(shape, dtype)
    if last == "A_log":
        # mamba1 init: A = -(1..N) broadcast over channels
        n = shape[-1]
        a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), shape)
        return jnp.log(a).astype(dtype)
    if last in ("fuse_attn_scale", "fuse_ssm_scale"):
        return jnp.full(shape, 0.5, dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, jnp.ndarray]:
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    return {
        name: _init_one(k, name, shape, dt)
        for k, (name, (shape, dt, _)) in zip(keys, sorted(shapes.items()))
    }


def param_pspecs(cfg: ModelConfig) -> Dict[str, P]:
    return {name: spec for name, (_, _, spec) in param_shapes(cfg).items()}


def abstract_params(
    cfg: ModelConfig, mesh: Mesh
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Sharded ShapeDtypeStructs for AOT lowering — no device allocation."""
    out = {}
    for name, (shape, dt, spec) in param_shapes(cfg).items():
        out[name] = jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, spec)
        )
    return out


def layer_tree(params: Dict[str, jnp.ndarray], prefix: str = "layers/"):
    """Sub-dict of stacked per-layer params (leading axis = layer)."""
    plen = len(prefix)
    return {k[plen:]: v for k, v in params.items() if k.startswith(prefix)}
