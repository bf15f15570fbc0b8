"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Each test lowers one kernel entry point with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler, at qwen3-8b's attention widths: 32 query / 8 KV heads, head_dim
128, bf16, S=4096. Mosaic refuses what interpret mode accepts -- a block
shape that does not tile the (8, 128) vreg, a kernel whose VMEM working set
does not fit -- so these run at no chip time and guard every change to the
kernels. Nothing runs: results and times need the chip (``chip_smoke.py``).
The last test compiles the paged serving engine's own decode and admission
programs at small widths and checks that they update the page pool in
place.

The topology is described only inside the module-scoped fixture: the TPU
library may be loaded by one process at a time, and test collection must
not depend on whether this worker got it.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.masks import MaskSpec
from repro.kernels import ops

B, S, HQ, HK, D = 1, 4096, 32, 8, 128
DT = jnp.bfloat16
SPECS = {
    "causal": MaskSpec(causal=True),
    "window1024": MaskSpec(causal=True, window=1024),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A described chip's programs are written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(shape, sharding, dtype=DT):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the compiled program
    after checking that a Mosaic kernel (not an interpreted one) is in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, seq=S):
    return (_shape((B, seq, HQ, D), sharding), _shape((B, seq, HK, D), sharding),
            _shape((B, seq, HK, D), sharding))


@pytest.mark.parametrize("spec", list(SPECS))
def test_forward_compiles(one_chip, spec):
    def fwd(q, k, v):
        return ops.flash_attention_pallas_with_lse(
            q, k, v, SPECS[spec], interpret=False)

    _compile(fwd, *_qkv(one_chip))


BWD_KERNELS = {"fused": ("fa2_bwd_fused",),
               "split": ("fa2_bwd_delta", "fa2_bwd_dkv", "fa2_bwd_dq")}


@pytest.mark.parametrize("bwd", list(BWD_KERNELS))
@pytest.mark.parametrize("spec", list(SPECS))
def test_forward_backward_compiles(one_chip, spec, bwd):
    """Each backward variant compiles as asked; the kernel names in the
    program prove which one ran."""
    def loss(q, k, v):
        o = ops.flash_attention_pallas(
            q, k, v, SPECS[spec], bwd=bwd, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))
    text = compiled.as_text()
    for other, names in BWD_KERNELS.items():
        for name in names:
            assert (name in text) == (other == bwd), (bwd, name)


def test_fused_backward_compiles_at_its_vmem_budget(one_chip):
    """The fused kernel's delta scratch is exactly ops'
    _FUSED_DELTA_VMEM_BUDGET (G * Sqp * 4 bytes) here, the largest the
    resolution keeps fused; the chip's compiler must still accept it."""
    from repro.kernels.ops import _FUSED_DELTA_VMEM_BUDGET

    hq, hk = 8, 1
    seq = _FUSED_DELTA_VMEM_BUDGET // (4 * hq)
    r = ops.resolve_pallas_knobs(
        ops.PallasFlashConfig(MaskSpec(causal=True), interpret=False),
        (B, seq, hq, D), (B, seq, hk, D), DT)
    assert r["bwd"] == "fused" and seq % r["block_q"] == 0

    def loss(q, k, v):
        o = ops.flash_attention_pallas(q, k, v, MaskSpec(causal=True),
                                       interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _shape((B, seq, hq, D), one_chip), _shape((B, seq, hk, D), one_chip),
        _shape((B, seq, hk, D), one_chip))
    assert "fa2_bwd_fused" in compiled.as_text()


def test_varlen_forward_backward_compiles(one_chip):
    """Packed documents (segment ids on the lane axis of q and kv tiles)
    and a lens-masked prefill (padding as segment 0), forward + backward."""
    def loss(q, k, v, seg):
        o = ops.flash_attention_pallas_varlen(
            q, k, v, seg, MaskSpec(causal=True), interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    def prefill(q, k, v, seg):
        return ops.flash_attention_pallas_varlen_with_lse(
            q, k, v, seg, MaskSpec(causal=True), interpret=False)

    seg = _shape((B, S), one_chip, jnp.int32)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip), seg)
    _compile(prefill, *_qkv(one_chip), seg)


def test_decode_compiles(one_chip):
    batch = 8

    def dec(q, k, v, lens):
        return ops.flash_decode_pallas(q, k, v, lens, interpret=False)

    _compile(dec, _shape((batch, 1, HQ, D), one_chip),
             _shape((batch, S, HK, D), one_chip),
             _shape((batch, S, HK, D), one_chip),
             _shape((batch,), one_chip, jnp.int32))


@pytest.mark.parametrize("batch,n_pages,pool,layers", [
    (8, S // 16, 8 * (S // 16) + 1, 1),  # every slot full, + the null page
    (32, 289, 4609, 9),  # the chat cell's engine: 32 slots, 289-page table
], ids=["full", "chat"])
def test_paged_decode_compiles(one_chip, batch, n_pages, pool, layers):
    """The paged kernel reads its layer of the stacked pool planes, the
    layer index a traced scalar as in the engine's layer scan."""
    ps = 16

    def dec(q, kp, vp, lens, tbl, layer):
        return ops.flash_decode_paged_pallas(q, kp, vp, lens, tbl, layer,
                                             interpret=False)

    _compile(dec, _shape((batch, 1, HQ, D), one_chip),
             _shape((layers, HK, pool, ps, D), one_chip),
             _shape((layers, HK, pool, ps, D), one_chip),
             _shape((batch,), one_chip, jnp.int32),
             _shape((batch, n_pages), one_chip, jnp.int32),
             _shape((), one_chip, jnp.int32))


# DeepSeek-V2-Lite's widths (the dsv2lite-serve.longctx cell): 16 heads of
# q/k width 192 and v width 128 in prefill; in decode one latent head of
# 512 + 64 lanes (padded to 640) read by 16 absorbed queries; 64 experts
# of width 1408.
MLA_H, MLA_QK, MLA_V, MLA_W, MLA_R = 16, 192, 128, 640, 512


def test_forward_narrow_v_compiles(one_chip):
    """The prefill forward with V narrower than Q/K."""
    def fwd(q, k, v):
        return ops.flash_attention_pallas_with_lse(
            q, k, v, MaskSpec(causal=True), interpret=False)

    compiled = _compile(
        fwd, _shape((1, S, MLA_H, MLA_QK), one_chip),
        _shape((1, S, MLA_H, MLA_QK), one_chip),
        _shape((1, S, MLA_H, MLA_V), one_chip))
    assert "fa2_fwd" in compiled.as_text()


def test_mla_decode_paged_compiles(one_chip):
    """The paged kernel's latent mode at the cell's engine: 32 slots, a
    1,857-page table, a 24,001-page pool, the 5 scanned layers' planes
    stacked."""
    batch, n_pages, pool, ps, layers = 32, 1857, 24001, 16, 5

    def dec(q, lat, lens, tbl, layer):
        return ops.mla_decode_paged_pallas(q, lat, lens, tbl, layer,
                                           v_dim=MLA_R, scale=0.1,
                                           interpret=False)

    compiled = _compile(dec, _shape((batch, 1, MLA_H, MLA_W), one_chip),
                        _shape((layers, 1, pool, ps, MLA_W), one_chip),
                        _shape((batch,), one_chip, jnp.int32),
                        _shape((batch, n_pages), one_chip, jnp.int32),
                        _shape((), one_chip, jnp.int32))
    assert "mla_decode_paged" in compiled.as_text()


@pytest.mark.parametrize("rows", [32 * 6, 28672 * 6], ids=["decode", "prefill"])
def test_moe_gmm_compiles(one_chip, rows):
    """The grouped matmul of the dropless MoE: gate/up (d -> d_expert) and
    down (d_expert -> d), at a decode step's and a full prefill launch's
    routed rows."""
    from repro.kernels.moe_gmm import gmm, tiling

    d, de, E = 2048, 1408, 64
    m = -(-rows // tiling(rows, d, de)[0]) * tiling(rows, d, de)[0]

    def ffn(x, wg, wd, sizes):
        h = gmm(x, wg, sizes, interpret=False)
        return gmm(h, wd, sizes, interpret=False)

    compiled = _compile(ffn, _shape((m, d), one_chip),
                        _shape((E, d, de), one_chip),
                        _shape((E, de, d), one_chip),
                        _shape((E,), one_chip, jnp.int32))
    assert "moe_gmm" in compiled.as_text()


# The paged serving engine's own programs, at small widths with the real
# head and latent widths: a Qwen3-shaped model of 3 scanned groups and a
# DeepSeek-V2-shaped one of a dense lead layer and 3 scanned MoE groups.
SERVE_CONFIGS = {
    "qwen3": lambda archs: dataclasses.replace(
        archs.QWEN3_8B, num_layers=3, d_model=256, num_heads=4,
        num_kv_heads=2, d_ff=512, vocab_size=512),
    "dsv2": lambda archs: dataclasses.replace(
        archs.DEEPSEEK_V2_LITE, num_layers=4, d_model=256, num_heads=4,
        num_kv_heads=4, d_ff=512, vocab_size=512,
        moe=dataclasses.replace(archs.DEEPSEEK_V2_LITE.moe, num_experts=8,
                                top_k=2, d_expert=256)),
}
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1}


def _largest_result(ty: str) -> int:
    """Bytes of the largest array in an HLO result type."""
    sizes = [0]
    for dt, dims in re.findall(r"\b([a-z0-9]+)\[([0-9,]*)\]", ty):
        if dt in _DTYPE_BYTES:
            sizes.append(math.prod(int(d) for d in dims.split(",") if d)
                         * _DTYPE_BYTES[dt])
    return max(sizes)


def _pool_copies(text: str, floor: int):
    """Instructions outside fusion bodies whose result holds an array of
    ``floor`` bytes or more and may be a fresh one, whatever their name:
    all but parameters, tuples and their elements, bitcasts, loops, and
    fusions whose root is a scatter (the in-place row and page writes)."""
    comps, roots, fused, cur = {}, {}, set(), None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            name = head.group(1)
        elif cur is not None and line.startswith("  "):
            cur.append(line)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
            root = re.match(r"\s+ROOT %?[\w.\-]+ = .*? ([a-z\-]+)\(", line)
            if root:
                roots[name] = root.group(1)
    kept = ("parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "constant")
    found = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z\-]+)\(",
                         line)
            if not m:
                continue
            name, ty, op = m.groups()
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            if op in kept or op == "fusion" and callee and \
                    roots.get(callee.group(1)) == "scatter":
                continue
            if _largest_result(ty) >= floor:
                found.append(f"{comp}: {name} {op} {ty[:60]}")
    return found


def _unaliased_pool_params(text: str, pool) -> list:
    """The entry parameters holding a pool leaf (found by shape) that
    ``input_output_alias`` does not hand to an output."""
    aliased = {int(n) for n in re.findall(
        r"\{[0-9, ]*\}: \((\d+), \{", text.splitlines()[0])}
    shapes = {",".join(map(str, a.shape)) for a in jax.tree.leaves(pool)}
    entry = text[text.index("\nENTRY "):]
    params = [(int(n), dims) for dims, n in re.findall(
        r"= [a-z0-9]+\[([0-9,]*)\]\S* parameter\((\d+)\)", entry)
        if dims in shapes]
    assert len(params) == len(jax.tree.leaves(pool)), params
    return [p for p in params if p[0] not in aliased]


@pytest.mark.parametrize("arch", list(SERVE_CONFIGS))
def test_paged_engine_steps_update_the_pool_in_place(one_chip, arch):
    """The engine's own decode step and an admission program, compiled
    for the chip, update the page pool in place: every pool leaf is
    aliased to an output (the steps are donated the pool); outside
    fusion bodies nothing but an in-place scatter produces an array as
    large as one layer's planes -- the layer scan neither slices a layer's
    planes out nor writes them back, and the new rows' write keeps the
    planes' layout, so no layout copy surrounds the kernel; and the
    program's scratch holds less than one layer's planes, so no write
    inside the scan makes a fresh plane either."""
    from repro.configs import archs
    from repro.core.attention import AttentionConfig
    from repro.models import lm
    from repro.serving.engine import PagedServingEngine

    cfg = SERVE_CONFIGS[arch](archs)
    B, n_max, ps, pad, W = 4, 16, 16, 64, 2
    attn = AttentionConfig(impl="flash_pallas", interpret=False,
                           decode_splits=2)
    eng = PagedServingEngine(cfg, None, attn, max_batch=B, num_pages=1025,
                             page_size=ps, pages_per_seq_max=n_max,
                             prompt_pad=pad)
    on_chip = lambda tree: jax.tree.map(
        lambda a: _shape(a.shape, one_chip, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: lm.init_lm(cfg, jax.random.PRNGKey(0))))
    pool = on_chip(eng.caches)
    i32 = lambda *dims: _shape(dims, one_chip, jnp.int32)
    # one layer's planes: the smallest layer slice of any pool leaf
    plane = min(a.nbytes // (a.shape[0] if a.ndim == 5 else 1)
                for a in jax.tree.leaves(eng.caches))
    programs = {
        "decode": eng._step.lower(params, i32(B, 1), pool, i32(B, n_max),
                                  i32(B)),
        "admit": eng._admit.lower(params, {"inputs": i32(W, pad),
                                           "lens": i32(W)},
                                  pool, i32(W, pad // ps)),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, name
        assert not _unaliased_pool_params(text, pool), name
        assert not _pool_copies(text, plane), (name, _pool_copies(text, plane))
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < plane, (name, temp, plane)
