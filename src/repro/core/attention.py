"""Unified attention entry point -- the framework's first-class feature.

Every model in ``repro.models`` calls :func:`attention` / :func:`decode_attention`;
the backend is selected by config, never by model code:

  impl = 'ref'           naive O(N^2)-memory attention (oracle / paper baseline)
  impl = 'flash_xla'     FA2 algorithm as XLA scans (CPU + dry-run path)
  impl = 'flash_pallas'  FA2 Pallas TPU kernel (interpret mode auto-enables
                         off-TPU; kernels/compat.resolve_interpret)

All three are exact and interchangeable; tests assert pairwise agreement.
The config carries only model-facing choices; the Pallas kernels' tile
schedule, backward and forward partitions resolve per shape in
``kernels/ops.resolve_pallas_knobs``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core import flash as _flash
from repro.core import decode as _decode
from repro.core.masks import MaskSpec


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    impl: str = "flash_xla"  # 'ref' | 'flash_xla' | 'flash_pallas'
    # None -> shape-aware defaults (kernels/ops.default_block_sizes) on the
    # Pallas path; the XLA scan path falls back to its fixed 512.
    block_q: Optional[int] = None
    block_kv: Optional[int] = None
    mode: str = "auto"  # tile schedule for flash_xla: 'dense' | 'packed' | 'auto'
    # Split-KV fan-out of the contiguous and the paged decodes.
    decode_splits: int = 8
    # Pallas interpret mode: None = auto (off on real TPUs, on elsewhere --
    # resolved in one place, kernels/compat.resolve_interpret).
    interpret: Optional[bool] = None


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    spec: MaskSpec,
    cfg: AttentionConfig = AttentionConfig(),
    *,
    scale: Optional[float] = None,
    segment_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Differentiable attention. q (B,Sq,Hq,D); k/v (B,Skv,Hkv,D) GQA.

    segment_ids (B, S) int32 enables packed varlen semantics on every
    backend (self-attention over one packed layout: q and kv share ids).

    Under ``attn_sharding='ring'`` rules (distributed/sharding.use_rules
    with a >1-wide model axis), self-attention calls route to the
    context-parallel ring implementation (distributed/ring_attention.py):
    same math, KV sharded instead of gathered. Cross-attention
    (Sq != Skv / q_offset) keeps the local path — its KV is encoder-sized
    and the 'sequence' gather handles it.
    """
    from repro.distributed.context_parallel import attn_context_mode

    if (
        attn_context_mode() == "ring"
        and cfg.impl in ("flash_pallas", "flash_xla")  # 'ref' stays the oracle
        and q.shape[1] == k.shape[1]
        and spec.q_offset == 0
    ):
        if segment_ids is not None:
            raise ValueError(
                "packed (varlen) attention does not compose with "
                "attn_sharding='ring' -- pack per data shard instead"
            )
        from repro.distributed.ring_attention import ring_flash_attention

        return ring_flash_attention(
            q, k, v, spec, impl=cfg.impl, scale=scale, block_q=cfg.block_q,
            block_kv=cfg.block_kv, interpret=cfg.interpret,
        )
    if cfg.impl == "ref":
        from repro.kernels.ref import attention_reference

        return attention_reference(q, k, v, spec, scale=scale, segment_ids=segment_ids)[0]
    if cfg.impl == "flash_xla":
        return _flash.flash_attention(
            q, k, v, spec, scale=scale, block_q=cfg.block_q or 512,
            block_kv=cfg.block_kv or 512, mode=cfg.mode, segment_ids=segment_ids,
        )
    if cfg.impl == "flash_pallas":
        if segment_ids is not None:
            from repro.kernels.ops import flash_attention_pallas_varlen

            return flash_attention_pallas_varlen(
                q, k, v, segment_ids, spec, scale=scale, block_q=cfg.block_q,
                block_kv=cfg.block_kv, interpret=cfg.interpret,
            )
        from repro.kernels.ops import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, spec, scale=scale, block_q=cfg.block_q, block_kv=cfg.block_kv,
            interpret=cfg.interpret,
        )
    raise ValueError(f"unknown attention impl: {cfg.impl}")


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_length: jnp.ndarray,
    cfg: AttentionConfig = AttentionConfig(),
    *,
    window: Optional[int] = None,
    sink: int = 0,
    scale: Optional[float] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    q_segment: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token decode against a padded KV cache. Returns (B,1,Hq,D).

    kv_segment_ids (B, S) + q_segment (B,) restrict the query to its own
    segment of a packed cache (see flash_decode / flash_decode_pallas).
    The split-KV fan-out is ``cfg.decode_splits``.
    """
    splits = cfg.decode_splits
    if cfg.impl == "flash_pallas":
        from repro.kernels.ops import flash_decode_pallas

        return flash_decode_pallas(
            q, k_cache, v_cache, cache_length, window=window, sink=sink, scale=scale,
            num_splits=splits, kv_segment_ids=kv_segment_ids,
            q_segment=q_segment, interpret=cfg.interpret,
        )[0]
    return _decode.flash_decode(
        q, k_cache, v_cache, cache_length, window=window, sink=sink, scale=scale,
        num_splits=splits, kv_segment_ids=kv_segment_ids,
        q_segment=q_segment,
    )[0]


def decode_attention_paged(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_pages: jnp.ndarray,  # (layers, Hkv, P, page_size, D) stacked planes
    v_pages: jnp.ndarray,
    cache_length: jnp.ndarray,  # (B,) int32 logical lengths
    block_table: jnp.ndarray,  # (B, n_pages) int32
    cfg: AttentionConfig = AttentionConfig(),
    *,
    layer=0,  # int32 scalar: the layer of the stack to read
    window: Optional[int] = None,
    sink: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-token decode against a *paged* KV cache. Returns (B,1,Hq,D).

    The cache is the pool's physical page planes, stacked over layers and
    read at ``layer``, plus a per-sequence block table
    (serving/kv_pool.py); rows with ``cache_length == 0`` (all-null
    table) read no KV at all on the Pallas path. The split-KV fan-out is
    ``cfg.decode_splits``."""
    splits = cfg.decode_splits
    if cfg.impl == "flash_pallas":
        from repro.kernels.ops import flash_decode_paged_pallas

        return flash_decode_paged_pallas(
            q, k_pages, v_pages, cache_length, block_table, layer,
            window=window, sink=sink, scale=scale, num_splits=splits,
            interpret=cfg.interpret,
        )[0]
    return _decode.flash_decode_paged(
        q, k_pages, v_pages, cache_length, block_table, layer,
        window=window, sink=sink, scale=scale, num_splits=splits,
    )[0]


def decode_attention_latent_paged(
    q: jnp.ndarray,  # (B, 1, H, W) absorbed latent queries
    latent_pages: jnp.ndarray,  # (layers, 1, P, page_size, W) stacked planes
    cache_length: jnp.ndarray,  # (B,) int32 logical lengths
    block_table: jnp.ndarray,  # (B, n_pages) int32
    cfg: AttentionConfig = AttentionConfig(),
    *,
    v_dim: int,
    scale: float,
    layer=0,  # int32 scalar: the layer of the stack to read
) -> jnp.ndarray:
    """Latent-attention decode against a paged latent cache. Returns
    (B, 1, H, v_dim): every head reads each cached latent token of width W
    once as its key, and its first ``v_dim`` lanes as its value
    (models/mla.py). The Pallas path is the paged decode kernel's latent
    mode (device events ``mla_decode_paged``); the XLA path runs the paged
    decode with the planes as V and keeps the first ``v_dim`` lanes."""
    splits = cfg.decode_splits
    if cfg.impl == "flash_pallas":
        from repro.kernels.ops import mla_decode_paged_pallas

        return mla_decode_paged_pallas(
            q, latent_pages, cache_length, block_table, layer, v_dim=v_dim,
            scale=scale, num_splits=splits, interpret=cfg.interpret,
        )[0]
    # An output lane reads only its own V lane: the lanes past ``v_dim``
    # are cut after, so the planes serve as V whole.
    return _decode.flash_decode_paged(
        q, latent_pages, latent_pages, cache_length, block_table, layer,
        scale=scale, num_splits=splits,
    )[0][..., :v_dim]
