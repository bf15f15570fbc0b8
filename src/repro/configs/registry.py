"""Arch registry: ``--arch`` lookup, shape applicability, input/cache specs.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of that (arch x shape) cell -- weak-type-correct, shardable, no
device allocation -- exactly what ``jit(...).lower()`` needs for the
multi-pod dry-run. ``cache_specs`` mirrors the model's decode-cache pytree
structure without running prefill.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.configs.base import SHAPES, ModelConfig, SSMConfig, ShapeConfig

_BY_NAME = {c.name: c for c in archs.ALL}


def names():
    return list(_BY_NAME)


def get(name: str) -> ModelConfig:
    if name not in _BY_NAME:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_BY_NAME)}")
    return _BY_NAME[name]


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


# --------------------------------------------------------------------------
# Shape applicability (DESIGN.md Section 4)
# --------------------------------------------------------------------------

_PURE_FULL_ATTN = {
    "qwen3-8b",
    "deepseek-coder-33b",
    "stablelm-12b",
    "internvl2-76b",
    "granite-moe-1b-a400m",
    "whisper-base",
    "deepseek-v2-lite",
}


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and cfg.name in _PURE_FULL_ATTN:
        return (
            "pure full-attention arch: long_500k requires sub-quadratic "
            "attention (skip noted in DESIGN.md Section 4)"
        )
    return None


# --------------------------------------------------------------------------
# Input specs
# --------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _enc_frames(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Encoder frame count for whisper per shape. Decode uses the 30s
    window (1500 frames) padded to 1536 so the context-parallel cache
    sharding (16-way seq split) divides evenly."""
    return 1536 if shape.kind == "decode" else shape.seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Model inputs for the given cell (train batch / prefill batch /
    decode step). Keys match launch.train/launch.serve signatures."""
    B, S = shape.global_batch, shape.seq_len
    act = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        specs = {
            "inputs": _sds((B, S), jnp.int32),
            "targets": _sds((B, S), jnp.int32),
        }
        if cfg.family == "encdec":
            specs["frames"] = _sds((B, _enc_frames(cfg, shape), cfg.d_model), act)
        if cfg.family == "vlm":
            specs["patches"] = _sds((B, cfg.num_patches, cfg.d_model), act)
        return specs
    if shape.kind == "prefill":
        specs = {"inputs": _sds((B, S), jnp.int32)}
        if cfg.family == "encdec":
            specs["frames"] = _sds((B, _enc_frames(cfg, shape), cfg.d_model), act)
        if cfg.family == "vlm":
            specs["patches"] = _sds((B, cfg.num_patches, cfg.d_model), act)
        return specs
    # decode: one token against a cache of S entries
    return {
        "token": _sds((B, 1), jnp.int32),
        "caches": cache_specs(cfg, B, S, enc_frames=_enc_frames(cfg, shape)),
        "cache_len": _sds((B,), jnp.int32),
    }


# --------------------------------------------------------------------------
# Cache specs (mirror lm.prefill / whisper.prefill output structure)
# --------------------------------------------------------------------------


def _layer_cache_spec(kind: str, cfg: ModelConfig, B: int, cache: int, act):
    if cfg.mla is not None:
        # one latent "KV head": [c_kv, rope(k_pe)] per token
        return {"latent": _sds((B, cache, 1, cfg.mla.cache_dim), act)}
    kv = {
        "k": _sds((B, cache, cfg.num_kv_heads, cfg.head_dim), act),
        "v": _sds((B, cache, cfg.num_kv_heads, cfg.head_dim), act),
    }
    if kind in ("attn", "attn_local"):
        return {"kv": kv}
    if kind == "mamba":
        return {"ssm": _ssm_state_spec(cfg, B, act)}
    return {"kv": kv, "ssm": _ssm_state_spec(cfg, B, act)}


def _ssm_state_spec(cfg: ModelConfig, B: int, act):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    return {
        "h": _sds((B, d_in, s.d_state), jnp.float32),
        "conv": _sds((B, s.d_conv - 1, d_in), act),
    }


def _stack(tree, n):
    return jax.tree.map(lambda x: _sds((n, *x.shape), x.dtype), tree)


def cache_specs(cfg: ModelConfig, B: int, cache: int, enc_frames: int = 1500):
    act = jnp.dtype(cfg.dtype)
    if cfg.family == "encdec":
        per_layer = {
            "kv": {
                "k": _sds((B, cache, cfg.num_kv_heads, cfg.head_dim), act),
                "v": _sds((B, cache, cfg.num_kv_heads, cfg.head_dim), act),
            },
            "cross": {
                "k": _sds((B, enc_frames, cfg.num_kv_heads, cfg.head_dim), act),
                "v": _sds((B, enc_frames, cfg.num_kv_heads, cfg.head_dim), act),
            },
        }
        return _stack(per_layer, cfg.num_layers)
    caches: Dict[str, Any] = {}
    if cfg.lead_pattern:
        caches["lead"] = [
            _layer_cache_spec(k, cfg, B, cache, act) for k in cfg.lead_pattern
        ]
    if cfg.num_groups:
        group = {
            f"slot_{u}": _layer_cache_spec(k, cfg, B, cache, act)
            for u, k in enumerate(cfg.layer_pattern)
        }
        if cfg.scan_layers and cfg.num_groups > 1:
            caches["groups"] = _stack(group, cfg.num_groups)
        else:
            caches["groups"] = [group for _ in range(cfg.num_groups)]
    if cfg.tail_pattern:
        caches["tail"] = [
            _layer_cache_spec(k, cfg, B, cache, act) for k in cfg.tail_pattern
        ]
    return caches


def paged_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int):
    """Cache tree for the *paged* serving engine: physical page planes
    ``(Hkv, num_pages, page_size, head_dim)`` per layer, shared by all
    resident sequences through one block table (serving/kv_pool.py). Same
    tree structure as :func:`cache_specs`, but every leaf is a stack of
    layer planes ``(layers, Hkv, num_pages, page_size, head_dim)``: the
    scanned groups' stacked, a lead, tail or unscanned layer's a stack of
    one. kernels/flash_decode.flash_decode_paged_kernel reads a stack at a
    layer index, and lm.decode_step carries the groups' stacks through its
    scan (DESIGN.md Section 5.3). Attention-only: a page holds no
    recurrent SSM state.

    Latent attention (``cfg.mla``) pages one plane per layer,
    ``(1, num_pages, page_size, mla.cache_dim)``: the normalised latent
    c_kv, the roped shared key k_pe, then zeros to a whole 128-lane tile,
    which the latent decode kernel reads once as K and, by its first
    ``kv_lora_rank`` lanes, as V."""
    act = jnp.dtype(cfg.dtype)
    kinds = tuple(cfg.layer_pattern) + tuple(cfg.tail_pattern)
    assert cfg.family != "encdec" and cfg.ssm is None and all(
        k in ("attn", "attn_local") for k in kinds
    ), "paged caches serve attention-only decoder configs"

    def planes(n: int):  # n layers' planes, stacked
        if cfg.mla is not None:
            shape = (n, 1, num_pages, page_size, cfg.mla.cache_dim)
            return {"latent": _sds(shape, act)}
        shape = (n, cfg.num_kv_heads, num_pages, page_size, cfg.head_dim)
        return {"kv": {"k": _sds(shape, act), "v": _sds(shape, act)}}

    def group(n: int):
        return {f"slot_{u}": planes(n) for u in range(len(cfg.layer_pattern))}

    caches: Dict[str, Any] = {}
    if cfg.lead_pattern:
        caches["lead"] = [planes(1) for _ in cfg.lead_pattern]
    if cfg.num_groups:
        if cfg.scan_layers and cfg.num_groups > 1:
            caches["groups"] = group(cfg.num_groups)
        else:
            caches["groups"] = [group(1) for _ in range(cfg.num_groups)]
    if cfg.tail_pattern:
        caches["tail"] = [planes(1) for _ in cfg.tail_pattern]
    return caches


# --------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# --------------------------------------------------------------------------


def reduce_config(cfg: ModelConfig) -> ModelConfig:
    """Same family/features, tiny dims: one fwd/train step runs on CPU."""
    heads = min(cfg.num_heads, 4) or 1
    kv = max(1, min(cfg.num_kv_heads, heads))
    while heads % kv:
        kv -= 1
    kw: Dict[str, Any] = dict(
        d_model=64,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=512,
        vocab_pad_to=64,
        window=32 if cfg.window else None,
        meta_tokens=8 if cfg.meta_tokens else 0,
        learned_pos_embed=128 if cfg.learned_pos_embed else None,
        max_seq_len=256,
        dtype="float32",
        num_patches=4 if cfg.num_patches else 0,
    )
    if cfg.mla is not None:
        from repro.configs.base import MLAConfig

        kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16)
        kw["head_dim"] = 24
        kw["num_kv_heads"] = heads
    lead = min(cfg.first_dense_layers, 1)
    kw["first_dense_layers"] = lead
    unit = cfg.layer_pattern
    if len(unit) == cfg.num_layers:  # unrolled pattern (hymba): shrink it
        kinds = sorted(set(unit), reverse=True)
        pattern = tuple(kinds) + (unit[1],) * (4 - len(set(unit)))
        kw["layer_pattern"] = pattern[:4]
        kw["num_layers"] = 4
    else:
        kw["layer_pattern"] = unit
        kw["num_layers"] = lead + len(unit) * 2 + (1 if cfg.tail_pattern else 0)
    if cfg.moe:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=min(cfg.moe.num_experts, 8),
            top_k=min(cfg.moe.top_k, 2),
            d_expert=64,
            capacity_factor=2.0,
        )
    if cfg.ssm:
        kw["ssm"] = SSMConfig(
            d_state=8, d_conv=4, expand=2,
            dt_rank=8, bcdt_norm=cfg.ssm.bcdt_norm,
        )
    if cfg.encoder:
        from repro.configs.base import EncoderConfig

        kw["encoder"] = EncoderConfig(num_layers=2, max_frames=64)
    return dataclasses.replace(cfg, name=cfg.name + "-reduced", **kw)
