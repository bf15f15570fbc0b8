"""A configuration file, as the program runs it, and the weights the
benchmark makes for it.

The file holds the published configuration's keys (``hidden_size``,
``num_hidden_layers``, ...) as run, with ``program.arch`` naming the
program's registry entry. :func:`program_config` takes that entry, sets the
depth and vocabulary the file states (and any ``program.override`` fields,
which the CPU tests use for tiny models), and refuses a file whose widths,
norm epsilon or rotary base are not what the program then runs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# published key -> the program's ModelConfig field, compared after the cut
_FIELDS = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_bias",
}
GAINS = ("scale", "q_norm", "k_norm")


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this file."""
    from repro.configs import registry

    prog = config["program"]
    cfg = registry.get(prog["arch"])
    cfg = dataclasses.replace(
        cfg, name=f"{prog['arch']}-{config['num_hidden_layers']}L",
        num_layers=config["num_hidden_layers"],
        vocab_size=config["vocab_size"], **prog.get("override", {}))
    for key, field in _FIELDS.items():
        if getattr(cfg, field) != config[key]:
            raise ValueError(f"{key} is {config[key]!r} in the file but the "
                             f"program runs {getattr(cfg, field)!r}")
    if jnp.dtype(cfg.dtype) != jnp.dtype(config["torch_dtype"]):
        raise ValueError(f"program dtype {cfg.dtype} is not the file's "
                         f"{config['torch_dtype']}")
    return cfg


def make_weights(cfg, key):
    """Weights in the program's tree layout and type, made on the device in
    one jitted call: gains 1, the embedding N(0, 1), every other matrix
    N(0, 1/fan_in). Only the layout is read from the program."""
    from repro.models import lm

    shapes = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name in GAINS:
                x = jnp.ones(s.shape, jnp.float32)
            elif name == "tokens":
                x = jax.random.normal(k, s.shape, jnp.float32)
            else:
                x = jax.random.normal(k, s.shape, jnp.float32) / math.sqrt(
                    s.shape[-2])
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(tdef, out)

    return jax.jit(init)(key)
