#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e (no chip) and print
the fullest device's bytes from the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/tools/memory.py --workload <name>

Training: the donated train step at the traffic's rows x sequence length.
Serving: the paged decode step and the largest admission program the
traffic can reach (widest warmed width, largest bucket), over the pool.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def report(name, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {gib(m.argument_size_in_bytes)}, outputs "
          f"{gib(m.output_size_in_bytes)}, aliased {gib(m.alias_size_in_bytes)}, "
          f"temporaries {gib(m.temp_size_in_bytes)}; fullest device "
          f"{gib(total)} ({total} bytes)", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import model
    from repro.core.attention import AttentionConfig
    from repro.launch import steps

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(ROOT, args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), t)
    cfg = model.program_config(cell.config)
    attn = AttentionConfig(impl=cell.config["program"]["attn_impl"],
                           interpret=False)
    params = put(jax.eval_shape(
        lambda: model.make_weights(cfg, jax.random.PRNGKey(0))))
    if cell.traffic["kind"] == "train_closed":
        from repro.training.optimizer import AdamWConfig, init_opt_state

        tr = cell.traffic
        opt = put(jax.eval_shape(init_opt_state, params))
        batch = put({k: jax.ShapeDtypeStruct((tr["rows"], tr["seq_len"]),
                                             jnp.int32)
                     for k in ("inputs", "targets")})
        step = jax.jit(steps.build_train_step(
            cfg, attn, AdamWConfig(**tr["optimizer"])), donate_argnums=(0, 1))
        report(f"train step {tr['rows']}x{tr['seq_len']}",
               step.lower(params, opt, batch).compile())
    else:
        from repro.configs.registry import paged_cache_specs

        eng, p = cell.config["engine"], cell.traffic["prompt"]
        caches = put(paged_cache_specs(cfg, eng["num_pages"], eng["page_size"]))
        B, n_max = eng["max_batch"], eng["pages_per_seq_max"]
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
        decode = jax.jit(steps.build_paged_serve_step(cfg, attn))
        report(f"decode step B={B} pool {eng['num_pages']} pages",
               decode.lower(params, i32(B, 1), caches, i32(B, n_max),
                            i32(B)).compile())
        W = max(eng["admit_widths_warmed"])
        pad = eng["prompt_pad"]
        bucket = min(-(-p["max"] // pad) * pad, n_max * eng["page_size"])
        admit = jax.jit(steps.build_paged_admit_step(cfg, attn,
                                                     eng["page_size"]))
        report(f"admit W={W} bucket {bucket}",
               admit.lower(params, {"inputs": i32(W, bucket), "lens": i32(W)},
                           caches, i32(W, -(-bucket // eng["page_size"])))
               .compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
