"""Generic decoder-only LM covering the dense / MoE / SSM / hybrid / VLM
families, with scan-over-layer-groups so HLO size is depth-independent.

A model is: embed (+ optional vision-patch prefix, + optional learnable
meta-token prefix) -> num_groups x layer_pattern (lax.scan, remat) ->
tail layers (unrolled) -> final norm. Heads:
  forward()      hidden states (loss/unembed applied by the caller so the
                 training loss can chunk the vocab dim)
  prefill()      hidden of the last position + per-layer decode caches
  decode_step()  one token in, logits + updated caches

Layer kinds (configs.base.LAYER_KINDS) pick the mixer: FA2 attention
(global or SWA; latent attention when cfg.mla is set), Mamba, or Hymba
hybrid. MoE replaces the MLP when cfg.moe is set, except in the
``cfg.first_dense_layers`` leading layers (params["lead"], unrolled before
the groups). Training routes MoE with capacity; prefill and decode route
dropless (models/moe.py) and, with ``with_stats``, return each MoE
layer's routing counters. All masks are MaskSpec-symbolic; meta tokens
become a `sink` prefix for windowed layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attention import AttentionConfig
from repro.core import masks as masks_mod
from repro.core.masks import MaskSpec
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.attention_layer import (
    apply_attention,
    decode_attention_step,
    init_attention,
    prefill_attention,
)
from repro.models.hybrid import (
    apply_hybrid,
    decode_hybrid_step,
    init_hybrid,
    prefill_hybrid,
)
from repro.models.mamba import apply_mamba, decode_mamba_step, init_mamba
from repro.models.mla import apply_mla, decode_mla_step, init_mla, prefill_mla
from repro.models.moe import STATS, apply_moe, apply_moe_dropless, init_moe


# ---------------------------------------------------------------------------
# Per-kind helpers
# ---------------------------------------------------------------------------


def _spec_for(cfg, kind: str) -> MaskSpec:
    window = cfg.kind_window(kind)
    sink = cfg.meta_tokens if (window is not None and cfg.meta_tokens) else 0
    return MaskSpec(causal=True, window=window, sink=sink)


def _theta_for(cfg, kind: str) -> float:
    if kind == "attn_local" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _has_mlp(cfg, kind: str) -> bool:
    return kind != "mamba"


def init_layer(kind: str, key, cfg, dtype, dense: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg, dtype)}
    if kind in ("attn", "attn_local") and cfg.mla is not None:
        p["mixer"] = init_mla(ks[0], cfg, dtype)
    elif kind in ("attn", "attn_local"):
        p["mixer"] = init_attention(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = init_mamba(ks[0], cfg, dtype)
    elif kind in ("hybrid", "hybrid_global"):
        p["mixer"] = init_hybrid(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if _has_mlp(cfg, kind):
        p["ln2"] = L.init_norm(cfg, dtype)
        if cfg.moe is not None and not dense:
            p["mlp"] = init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg, cfg.d_ff, dtype)
    return p


def _is_moe(p) -> bool:
    return "router" in p["mlp"]


def _apply_mlp_block(p, cfg, x):
    """Second residual sub-block (training: capacity routing); returns
    (delta, aux)."""
    h = L.apply_norm(p["ln2"], x, cfg.norm_eps, cfg.norm)
    if _is_moe(p):
        return apply_moe(p["mlp"], cfg, h)
    return L.apply_mlp(p["mlp"], h, cfg.mlp), jnp.zeros((), jnp.float32)


def _serve_mlp_block(p, cfg, x, valid):
    """Second residual sub-block of prefill and decode: dropless routing
    over the ``valid`` tokens. Returns (delta, stats (3,) int32 or None
    for a dense MLP)."""
    h = L.apply_norm(p["ln2"], x, cfg.norm_eps, cfg.norm)
    if _is_moe(p):
        return apply_moe_dropless(p["mlp"], cfg, h, valid)
    return L.apply_mlp(p["mlp"], h, cfg.mlp), None


def _stack_stats(stats):
    """Routing counters of the MoE layers, (n, len(STATS)) int32."""
    stats = [s for s in stats if s is not None]
    if not stats:
        return jnp.zeros((0, len(STATS)), jnp.int32)
    return jnp.concatenate([s.reshape(-1, len(STATS)) for s in stats])


def apply_layer(
    kind, p, cfg, x, positions, attn_cfg, segment_ids=None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps, cfg.norm)
    spec = _spec_for(cfg, kind)
    if kind in ("attn", "attn_local") and cfg.mla is not None:
        mix = apply_mla(p["mixer"], cfg, h, positions, spec, attn_cfg,
                        segment_ids=segment_ids)
    elif kind in ("attn", "attn_local"):
        mix = apply_attention(
            p["mixer"], cfg, h, positions, spec, attn_cfg,
            rope_theta=_theta_for(cfg, kind), segment_ids=segment_ids,
        )
    elif kind == "mamba":
        if segment_ids is not None:
            raise ValueError("packed (varlen) mode supports attention layers only; "
                             f"got layer kind {kind!r} (SSM state crosses segments)")
        mix = apply_mamba(p["mixer"], cfg, h, remat=cfg.remat)
    else:
        if segment_ids is not None:
            raise ValueError("packed (varlen) mode supports attention layers only; "
                             f"got layer kind {kind!r}")
        mix = apply_hybrid(
            p["mixer"], cfg, h, positions, spec, attn_cfg,
            rope_theta=_theta_for(cfg, kind), remat=cfg.remat,
        )
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if _has_mlp(cfg, kind):
        delta, aux = _apply_mlp_block(p, cfg, x)
        x = x + delta
    return constrain(x, "batch", "seq", "embed"), aux


def prefill_layer(kind, p, cfg, x, positions, attn_cfg, cache_size, valid=None):
    """-> (x, cache, MoE stats or None); ``valid`` (B, S) marks real tokens."""
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps, cfg.norm)
    spec = _spec_for(cfg, kind)
    if kind in ("attn", "attn_local") and cfg.mla is not None:
        mix, cache = prefill_mla(p["mixer"], cfg, h, positions, spec, attn_cfg,
                                 cache_size=cache_size)
    elif kind in ("attn", "attn_local"):
        mix, cache = prefill_attention(
            p["mixer"], cfg, h, positions, spec, attn_cfg,
            rope_theta=_theta_for(cfg, kind), cache_size=cache_size,
        )
        cache = {"kv": cache}
    elif kind == "mamba":
        mix, ssm = apply_mamba(p["mixer"], cfg, h, remat=cfg.remat, return_state=True)
        cache = {"ssm": ssm}
    else:
        mix, cache = prefill_hybrid(
            p["mixer"], cfg, h, positions, spec, attn_cfg,
            rope_theta=_theta_for(cfg, kind), cache_size=cache_size, remat=cfg.remat,
        )
    x = x + mix
    stats = None
    if _has_mlp(cfg, kind):
        delta, stats = _serve_mlp_block(p, cfg, x, valid)
        x = x + delta
    return constrain(x, "batch", "seq", "embed"), cache, stats


def decode_layer(kind, p, cfg, x, cache, cache_len, attn_cfg, block_table=None,
                 layer=0):
    """-> (x, new cache, MoE stats or None). Paged (``block_table``), the
    cache leaves are stacks of layer planes and this layer's is ``layer``."""
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps, cfg.norm)
    spec = _spec_for(cfg, kind)
    theta = _theta_for(cfg, kind)
    if kind in ("attn", "attn_local") and cfg.mla is not None:
        mix, new_cache = decode_mla_step(p["mixer"], cfg, h, cache, cache_len,
                                         attn_cfg, block_table=block_table,
                                         layer=layer)
    elif kind in ("attn", "attn_local"):
        mix, kv = decode_attention_step(
            p["mixer"], cfg, h, cache["kv"], cache_len, attn_cfg,
            rope_theta=theta, window=spec.window, sink=spec.sink,
            block_table=block_table, layer=layer,
        )
        new_cache = {"kv": kv}
    elif kind == "mamba":
        if block_table is not None:
            raise ValueError("paged decode serves attention layers only "
                             f"(got layer kind {kind!r})")
        mix, ssm = decode_mamba_step(p["mixer"], cfg, h, cache["ssm"])
        new_cache = {"ssm": ssm}
    else:
        if block_table is not None:
            raise ValueError("paged decode serves attention layers only "
                             f"(got layer kind {kind!r})")
        mix, new_cache = decode_hybrid_step(
            p["mixer"], cfg, h, cache, cache_len, attn_cfg,
            rope_theta=theta, window=spec.window, sink=spec.sink,
        )
    x = x + mix
    stats = None
    if _has_mlp(cfg, kind):
        # empty slots (cache_len 0) route to no expert
        delta, stats = _serve_mlp_block(p, cfg, x, (cache_len > 0)[:, None])
        x = x + delta
    return x, new_cache, stats


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def init_lm(cfg, key, dtype=None) -> dict:
    cfg.validate()
    dtype = dtype or jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.init_embedding(keys[0], cfg, dtype),
        "ln_f": L.init_norm(cfg, dtype),
    }
    if cfg.meta_tokens:
        params["meta"] = L._normal(keys[1], (cfg.meta_tokens, cfg.d_model), 0.02, dtype)

    if cfg.lead_pattern:
        lks = jax.random.split(keys[4], len(cfg.lead_pattern))
        params["lead"] = [init_layer(kind, lks[i], cfg, dtype, dense=True)
                          for i, kind in enumerate(cfg.lead_pattern)]
    U, NG = cfg.group_size, cfg.num_groups
    if NG:
        def init_group(gkey):
            gks = jax.random.split(gkey, U)
            return {f"slot_{u}": init_layer(cfg.layer_pattern[u], gks[u], cfg, dtype)
                    for u in range(U)}

        group_keys = jax.random.split(keys[2], NG)
        if cfg.scan_layers and NG > 1:
            params["groups"] = jax.vmap(init_group)(group_keys)
        else:
            params["groups"] = [init_group(k) for k in group_keys]
    tail = cfg.tail_pattern
    if tail:
        tks = jax.random.split(keys[3], len(tail))
        params["tail"] = [init_layer(kind, tks[i], cfg, dtype) for i, kind in enumerate(tail)]
    return params


def _embed_inputs(cfg, params, tokens, patches=None):
    """tokens (B,S) [+ patches (B,P,d)] -> (h, positions, n_prefix)."""
    h = L.embed_tokens(params["embed"], tokens)
    if cfg.embed_scale_by_dim:
        h = (h.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(h.dtype)
    parts = []
    if patches is not None:
        parts.append(patches.astype(h.dtype))
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(
            params["meta"][None], (h.shape[0], cfg.meta_tokens, cfg.d_model)
        )
        parts = [meta] + parts
    if parts:
        h = jnp.concatenate(parts + [h], axis=1)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.learned_pos_embed:
        h = h + params["embed"]["positions"][:S][None].astype(h.dtype)
    n_prefix = S - tokens.shape[1]
    return constrain(h, "batch", "seq", "embed"), positions, n_prefix


def _run_groups(cfg, params, h, positions, attn_cfg, segment_ids=None):
    """Scan the grouped layers; returns (h, aux_sum)."""
    U = cfg.group_size
    aux0 = jnp.zeros((), jnp.float32)

    def group_body(carry, gp):
        x, aux = carry
        for u, kind in enumerate(cfg.layer_pattern):
            x, a = apply_layer(
                kind, gp[f"slot_{u}"], cfg, x, positions, attn_cfg, segment_ids
            )
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(group_body, prevent_cse=False) if cfg.remat else group_body
    for i, kind in enumerate(cfg.lead_pattern):
        h, a = apply_layer(kind, params["lead"][i], cfg, h, positions, attn_cfg, segment_ids)
        aux0 = aux0 + a
    if cfg.num_groups:
        if cfg.scan_layers and cfg.num_groups > 1:
            (h, aux0), _ = jax.lax.scan(body, (h, aux0), params["groups"])
        else:
            gs = params["groups"]
            for gp in gs:
                (h, aux0), _ = body((h, aux0), gp)
    for i, kind in enumerate(cfg.tail_pattern):
        h, a = apply_layer(kind, params["tail"][i], cfg, h, positions, attn_cfg, segment_ids)
        aux0 = aux0 + a
    return h, aux0


def forward(cfg, params, tokens, attn_cfg: AttentionConfig, patches=None,
            segment_ids=None):
    """-> (hidden (B, S_total, d), aux_loss, n_prefix). Caller unembeds.

    segment_ids (B, S) int32 turns on packed (varlen) training: attention
    stays within segments and RoPE positions restart at each segment start.
    Incompatible with patches/meta-token prefixes (no prefix in packed rows).
    """
    h, positions, n_prefix = _embed_inputs(cfg, params, tokens, patches)
    if segment_ids is not None:
        assert n_prefix == 0, "packed mode does not support prefix tokens"
        assert not cfg.learned_pos_embed, "packed mode needs RoPE positions"
        positions = masks_mod.segment_positions(segment_ids)
    h, aux = _run_groups(cfg, params, h, positions, attn_cfg, segment_ids)
    h = L.apply_norm(params["ln_f"], h, cfg.norm_eps, cfg.norm)
    return h, aux, n_prefix


def logits_from_hidden(cfg, params, hidden):
    return L.unembed(params["embed"], hidden, cfg.tie_embeddings)


# ------------------------------------------------------------------ serving


def prefill(cfg, params, tokens, attn_cfg: AttentionConfig, cache_size: int,
            patches=None, lens=None, with_stats: bool = False):
    """-> (hidden_last (B,1,d), caches, lens_total (B,) int32[, stats]).
    Caches are per-layer trees stacked over groups; cache_size is the
    padded KV capacity.

    ``lens`` (B,) int32 marks the true token count per row when ``tokens``
    is right-padded to a bucket length (serving admission): the returned
    hidden is taken at each row's last *real* position and ``lens_total``
    counts only real tokens (+ any prefix). Causality keeps padding out of
    the real positions' attention, dropless MoE layers route no padding,
    and the caller masks the padded cache tail via its per-slot cache
    length. Not supported for SSM/hybrid configs (recurrent state would
    consume the padding). ``with_stats`` also returns the MoE layers'
    routing counters over the real tokens, (n_moe_layers, 3) int32
    (models/moe.STATS).
    """
    if lens is not None and cfg.ssm is not None:
        raise ValueError("lens-padded prefill needs attention-only configs "
                         "(SSM state crosses the padding)")
    h, positions, n_prefix = _embed_inputs(cfg, params, tokens, patches)
    valid = None
    if lens is not None:
        valid = positions[None, :] < (n_prefix + lens.astype(jnp.int32))[:, None]

    def layer(kind, p, x):
        return prefill_layer(kind, p, cfg, x, positions, attn_cfg, cache_size,
                             valid)

    def group_body(x, gp):
        caches, stats = {}, []
        for u, kind in enumerate(cfg.layer_pattern):
            x, caches[f"slot_{u}"], st = layer(kind, gp[f"slot_{u}"], x)
            stats.append(st)
        return x, (caches, _stack_stats(stats))

    h, caches, stats = _serve_layers(cfg, params, h, layer, group_body)
    h = L.apply_norm(params["ln_f"], h, cfg.norm_eps, cfg.norm)
    B = h.shape[0]
    if lens is None:
        out = (h[:, -1:], caches, jnp.full((B,), h.shape[1], jnp.int32))
    else:
        lens = lens.astype(jnp.int32)
        last = (n_prefix + lens - 1)[:, None, None]  # (B,1,1) last real position
        h_last = jnp.take_along_axis(
            h, jnp.broadcast_to(last, (B, 1, h.shape[2])), axis=1)
        out = (h_last, caches, n_prefix + lens)
    return out + (stats,) if with_stats else out


def _serve_layers(cfg, params, h, layer, group_body, caches_in=None,
                  carry=False):
    """Run lead, grouped and tail layers of prefill (``caches_in`` None)
    or decode: ``layer(kind, p, x[, cache])`` -> (x, cache, stats) and
    ``group_body`` the scan body over groups. Returns (h, caches, stats
    (n_moe_layers, 3)).

    ``carry`` (paged decode): the scanned groups' caches ride in the scan's
    carry, whole, with the group index ``g``, and ``group_body(x, (gp,
    caches), g)`` updates them at ``g``: no group's planes are sliced out
    as ``xs`` or written back as ``ys``."""
    caches: Dict[str, Any] = {}
    stats = []
    with_cache = caches_in is not None
    if cfg.lead_pattern:
        caches["lead"] = []
        for i, kind in enumerate(cfg.lead_pattern):
            args = (caches_in["lead"][i],) if with_cache else ()
            h, c, st = layer(kind, params["lead"][i], h, *args)
            caches["lead"].append(c)
            stats.append(st)
    if cfg.num_groups:
        xs = (params["groups"], caches_in["groups"]) if with_cache else params["groups"]
        if cfg.scan_layers and cfg.num_groups > 1 and carry:
            def body(c, gp):
                x, stack, g = c
                x, (stack, st) = group_body(x, (gp, stack), g)
                return (x, stack, g + 1), st

            (h, caches["groups"], _), st = jax.lax.scan(
                body, (h, caches_in["groups"], jnp.int32(0)), params["groups"])
            stats.append(st)
        elif cfg.scan_layers and cfg.num_groups > 1:
            h, (caches["groups"], st) = jax.lax.scan(group_body, h, xs)
            stats.append(st)
        else:
            caches["groups"] = []
            for g in range(cfg.num_groups):
                x_g = (xs[0][g], xs[1][g]) if with_cache else xs[g]
                h, (c, st) = group_body(h, x_g)
                caches["groups"].append(c)
                stats.append(st)
    if cfg.tail_pattern:
        caches["tail"] = []
        for i, kind in enumerate(cfg.tail_pattern):
            args = (caches_in["tail"][i],) if with_cache else ()
            h, c, st = layer(kind, params["tail"][i], h, *args)
            caches["tail"].append(c)
            stats.append(st)
    return h, caches, _stack_stats(stats)


def decode_step(cfg, params, token, caches, cache_len, attn_cfg: AttentionConfig,
                block_table=None, with_stats: bool = False):
    """token (B,1) int32; cache_len (B,) valid entries per sequence.
    -> (logits (B,1,V), new_caches[, stats]).

    ``block_table`` (B, n_pages) int32 switches every attention layer to
    the paged cache path (pool page planes instead of per-slot contiguous
    caches -- see attention_layer.decode_attention_step); the table is
    shared by all layers. The pool is then updated in place: the scanned
    groups' stacked planes are carried through the scan, each layer
    writing its new rows into them and reading them at its group index,
    and a lead, tail or unscanned layer's stack of one is read at index 0
    (registry.paged_cache_specs, DESIGN.md Section 5.3). ``with_stats``
    also returns the MoE layers' routing counters over the live rows
    (cache_len > 0), (n_moe_layers, 3) int32 (models/moe.STATS)."""
    h = L.embed_tokens(params["embed"], token)
    if cfg.embed_scale_by_dim:
        h = (h.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(h.dtype)
    if cfg.learned_pos_embed:
        pos_e = jnp.take(params["embed"]["positions"], cache_len, axis=0)[:, None]
        h = h + pos_e.astype(h.dtype)

    def layer(kind, p, x, cache, g=0):
        return decode_layer(kind, p, cfg, x, cache, cache_len, attn_cfg,
                            block_table, g)

    def group_body(x, gp_cache, g=0):
        gp, cache = gp_cache
        new_caches, stats = {}, []
        for u, kind in enumerate(cfg.layer_pattern):
            x, new_caches[f"slot_{u}"], st = layer(
                kind, gp[f"slot_{u}"], x, cache[f"slot_{u}"], g)
            stats.append(st)
        return x, (new_caches, _stack_stats(stats))

    h, new_caches, stats = _serve_layers(cfg, params, h, layer, group_body,
                                         caches, carry=block_table is not None)
    h = L.apply_norm(params["ln_f"], h, cfg.norm_eps, cfg.norm)
    logits = logits_from_hidden(cfg, params, h)
    return (logits, new_caches, stats) if with_stats else (logits, new_caches)
