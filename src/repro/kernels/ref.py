"""Pure-jnp reference oracle for attention (forward and the Algorithm-2 bwd).

This is the ground truth every other implementation (XLA flash, Pallas
kernels, decode paths, context-parallel attention) is tested against.
It deliberately materializes the N x N score matrix -- O(N^2) memory --
and is also the "standard attention" baseline of the paper's benchmarks.

Layout convention (whole repo): q (B, Sq, Hq, D); k, v (B, Skv, Hkv, D)
with Hq % Hkv == 0 (GQA). Output (B, Sq, Hq, D); LSE (B, Hq, Sq). The
forward also takes V of another width Dv (latent attention): its output is
then (B, Sq, Hq, Dv).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.masks import MaskSpec, make_segment_mask, make_tile_mask


def attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    spec: MaskSpec = MaskSpec(),
    scale: Optional[float] = None,
    kv_length: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Naive exact attention. Returns (o, lse).

    kv_length: optional (B,) int32 of valid KV lengths (for padded caches).
    segment_ids / kv_segment_ids: optional (B, Sq) / (B, Sk) int32 packed
    varlen ids -- visibility additionally requires equal ids (the dense
    ground truth the varlen kernels are tested against).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hk, _ = k.shape
    assert Hq % Hk == 0, (Hq, Hk)
    G = Hq // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    qf = qf.reshape(B, Sq, Hk, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)  # (B, Hk, G, Sq, Sk)

    q_ids = jnp.arange(Sq, dtype=jnp.int32) + spec.q_offset
    kv_ids = jnp.arange(Sk, dtype=jnp.int32)
    mask = make_tile_mask(spec, q_ids, kv_ids)  # (Sq, Sk) or None
    if segment_ids is not None:
        if kv_segment_ids is None:
            kv_segment_ids = segment_ids
        seg = make_segment_mask(segment_ids, kv_segment_ids)  # (B, Sq, Sk)
        seg = seg[:, None, None]  # broadcast over (Hk, G)
        mask = seg if mask is None else (mask & seg)
    if kv_length is not None:
        valid = kv_ids[None, :] < kv_length[:, None]  # (B, Sk)
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)

    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)  # exact zeros for masked entries
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p / l_safe[..., None], vf)
    lse = jnp.where(l == 0.0, -jnp.inf, m_safe + jnp.log(l_safe))
    return (
        o.reshape(B, Sq, Hq, vf.shape[-1]).astype(q.dtype),
        lse.reshape(B, Hk * G, Sq),
    )


def attention_reference_bwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    o: jnp.ndarray,
    do: jnp.ndarray,
    lse: jnp.ndarray,
    spec: MaskSpec = MaskSpec(),
    scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reference backward implementing the paper's Section 2.2 equations,
    recomputing P from (q, k, lse) exactly as Algorithm 2 does.

    Returns (dq, dk, dv). Used to sanity-check custom VJPs independently of
    jax.grad through the reference forward.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hk, _ = k.shape
    G = Hq // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qf = q.astype(jnp.float32).reshape(B, Sq, Hk, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32).reshape(B, Sq, Hk, G, D)
    of = o.astype(jnp.float32).reshape(B, Sq, Hk, G, D)
    lsef = lse.reshape(B, Hk, G, Sq)

    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf * scale, kf)
    q_ids = jnp.arange(Sq, dtype=jnp.int32) + spec.q_offset
    kv_ids = jnp.arange(Sk, dtype=jnp.int32)
    mask = make_tile_mask(spec, q_ids, kv_ids)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    # P = exp(S - L): Algorithm 2 line 11 -- the FA2 tweak (LSE only).
    p = jnp.exp(s - jnp.where(jnp.isneginf(lsef), 0.0, lsef)[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)

    dv = jnp.einsum("bhgqk,bqhgd->bkhd", p, dof)
    dp = jnp.einsum("bqhgd,bkhd->bhgqk", dof, vf)
    delta = jnp.sum(dof * of, axis=-1)  # D = rowsum(dO o O), line 4
    ds = p * (dp - delta.transpose(0, 2, 3, 1)[..., None])
    dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * scale
    dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qf) * scale
    return (
        dq.reshape(B, Sq, Hq, D).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )
