"""Step builders: the jit'd train / prefill / serve step for any arch.

These close over (ModelConfig, AttentionConfig, AdamWConfig) and present
uniform signatures across all 10 architectures:

  train_step(params, opt_state, batch)        -> (params, opt_state, metrics)
  prefill_step(params, batch)                 -> (next_token, caches, lens)
  serve_step(params, token, caches, cache_len)-> (next_token, new_caches)

Gradient accumulation: ``microbatches > 1`` scans over batch slices
accumulating fp32 grads (same numerics as one big batch; the loss is
token-mean so we average the per-micro grads).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import AttentionConfig
from repro.core.decode import paged_write
from repro.models import lm, whisper
from repro.training.losses import chunked_cross_entropy
from repro.training.optimizer import AdamWConfig, OptState, apply_updates


def _embed_params(cfg: ModelConfig, params):
    return params["decoder"]["embed"] if cfg.family == "encdec" else params["embed"]


def loss_fn(cfg: ModelConfig, attn_cfg: AttentionConfig, params, batch, ce_chunk: int = 512):
    if cfg.family == "encdec":
        hidden, aux, nprefix = whisper.forward(
            cfg, params, batch["frames"], batch["inputs"], attn_cfg
        )
    else:
        hidden, aux, nprefix = lm.forward(
            cfg, params, batch["inputs"], attn_cfg, patches=batch.get("patches"),
            segment_ids=batch.get("segment_ids"),
        )
    if nprefix:
        hidden = hidden[:, nprefix:]
    loss, metrics = chunked_cross_entropy(
        _embed_params(cfg, params), cfg.tie_embeddings, hidden, batch["targets"],
        vocab_valid=cfg.vocab_size, mask=batch.get("loss_mask"), chunk=ce_chunk,
    )
    return loss + aux, {"ce_loss": loss, "aux_loss": aux, **metrics}


def build_train_step(
    cfg: ModelConfig,
    attn_cfg: AttentionConfig,
    opt_cfg: AdamWConfig,
    *,
    microbatches: int = 1,
    ce_chunk: int = 512,
):
    grad_fn = jax.value_and_grad(
        functools.partial(loss_fn, cfg, attn_cfg, ce_chunk=ce_chunk),
        argnums=0, has_aux=True,
    )

    def train_step(params, opt_state: OptState, batch):
        if microbatches > 1:
            B = batch["inputs"].shape[0]
            assert B % microbatches == 0

            def split(t):
                return t.reshape(microbatches, B // microbatches, *t.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                g_acc, l_acc, m_acc = acc
                (l, m), g = grad_fn(params, mb)
                g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g, l_acc + l, {k: m_acc[k] + m[k] for k in m_acc}), None

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
            m0 = {k: jnp.zeros((), jnp.float32)
                  for k in ("ce_loss", "aux_loss", "nll_sum", "tokens", "accuracy")}
            (grads, loss, metrics), _ = jax.lax.scan(
                body, (g0, jnp.zeros(()), m0), micro
            )
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            metrics = {k: v / microbatches for k, v in metrics.items()}
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        # Gradient sync dtype: grads reach here already in the compute dtype
        # (bf16 -- jax cotangent dtype rules), which is the brief's gradient
        # compression. NOTE (EXPERIMENTS.md Section Perf, deepseek iters 5a/5b):
        # XLA's partitioner still all-reduces the per-layer partials in fp32
        # inside the backward scan; neither a post-hoc cast nor an
        # optimization_barrier moved it -- both hypotheses refuted, recorded.
        new_params, new_opt, om = apply_updates(
            opt_cfg, opt_state, grads, param_dtype=jnp.dtype(cfg.dtype)
        )
        return new_params, new_opt, {"loss": loss, **metrics, **om}

    return train_step


def build_prefill_step(cfg: ModelConfig, attn_cfg: AttentionConfig, cache_size: int):
    def prefill_step(params, batch):
        if cfg.family == "encdec":
            from repro.models.layers import unembed

            h_last, caches, tlen = whisper.prefill(
                cfg, params, batch["frames"], batch["inputs"], attn_cfg, cache_size
            )
            logits = unembed(params["decoder"]["embed"], h_last, cfg.tie_embeddings)
            lens = jnp.full((logits.shape[0],), tlen, jnp.int32)
        else:
            # batch['lens'] (B,) marks true token counts for bucket-padded
            # prompts (ServingEngine admission); lm.prefill then selects the
            # hidden at each row's last real position.
            h_last, caches, lens = lm.prefill(
                cfg, params, batch["inputs"], attn_cfg, cache_size,
                patches=batch.get("patches"), lens=batch.get("lens"),
            )
            logits = lm.logits_from_hidden(cfg, params, h_last)
        next_token = jnp.argmax(logits[..., : cfg.vocab_size], axis=-1).astype(jnp.int32)
        return next_token, caches, lens

    return prefill_step


def build_serve_step(cfg: ModelConfig, attn_cfg: AttentionConfig):
    def serve_step(params, token, caches, cache_len):
        if cfg.family == "encdec":
            logits, new_caches = whisper.decode_step(
                cfg, params, token, caches, cache_len, attn_cfg
            )
        else:
            logits, new_caches = lm.decode_step(
                cfg, params, token, caches, cache_len, attn_cfg
            )
        next_token = jnp.argmax(logits[..., : cfg.vocab_size], axis=-1).astype(jnp.int32)
        return next_token, new_caches

    return serve_step


def build_paged_serve_step(cfg: ModelConfig, attn_cfg: AttentionConfig):
    """Decode step over the paged cache. All shapes are functions of
    (max_batch, pages_per_seq_max, page_size) only -- never of which
    requests are resident -- so the jitted step compiles exactly once and
    requests join/leave with zero recompiles (pinned by
    tests/test_paged.py). An MoE config also returns its MoE layers'
    routing counters, (n_moe_layers, 3) int32 (models/moe.STATS)."""
    with_stats = cfg.moe is not None

    def paged_serve_step(params, token, caches, block_table, cache_len):
        out = lm.decode_step(
            cfg, params, token, caches, cache_len, attn_cfg,
            block_table=block_table, with_stats=with_stats,
        )
        next_token = jnp.argmax(out[0][..., : cfg.vocab_size], axis=-1).astype(jnp.int32)
        return (next_token,) + tuple(out[1:])

    return paged_serve_step


def build_paged_admit_step(cfg: ModelConfig, attn_cfg: AttentionConfig,
                           page_size: int):
    """Batched admission: one lens-masked bucketed prefill for a whole
    same-bucket group, its contiguous caches written straight into the
    pool's page planes at the ``dest`` physical pages, every layer's in
    one page write (core.decode.paged_write, in place once the pool is
    donated).

    ``batch['inputs']`` (W, pad_to) right-padded prompts, ``batch['lens']``
    (W,) true lengths, ``dest`` (W, pad_to_pages) int32 physical page per
    logical prefill page (0 = the null page for rows/pages that must not
    land anywhere -- width-padding rows and overflow). Shapes depend only
    on (pad_to, W), so jit compiles once per (bucket, admission width).
    An MoE config also returns the routing counters, as the decode step."""
    with_stats = cfg.moe is not None

    def scatter(paged, contig, dest):
        # contig ([L,] W, S, Hk, hd) -> pages (L, W, NP, Hk, ps, hd) for the
        # pool's stack of L layers' planes
        L = paged.shape[0]
        W, S, Hk, hd = contig.shape[-4:]
        pages = contig.reshape(L, W, S // page_size, page_size, Hk, hd)
        pages = jnp.moveaxis(pages, 4, 3)
        return paged_write(paged, jnp.arange(L)[:, None, None], dest[None],
                           pages)

    def admit_step(params, batch, caches, dest):
        tokens = batch["inputs"]
        cache_size = -(-tokens.shape[1] // page_size) * page_size
        h_last, prefill_caches, lens_total, *stats = lm.prefill(
            cfg, params, tokens, attn_cfg, cache_size, lens=batch.get("lens"),
            with_stats=with_stats,
        )
        logits = lm.logits_from_hidden(cfg, params, h_last)
        next_token = jnp.argmax(
            logits[..., : cfg.vocab_size], axis=-1
        ).astype(jnp.int32)
        new_caches = jax.tree.map(
            functools.partial(scatter, dest=dest), caches, prefill_caches
        )
        return (next_token, lens_total, new_caches, *stats)

    return admit_step
