"""Weights from ``--seed``: the served tree in one jitted call on the
device, in bfloat16; and the same leaves one layer at a time for the plain
reference, which regenerates them and takes nothing the program has made.

Every leaf has a key of its own, folded from (seed, layer, leaf name), so a
leaf's bits do not depend on what else is made in the same call.

The seed reaches a jitted program as an ARGUMENT (``seed_words``), never as
a constant closed over: a program with the seed baked in is another program
for every seed, and compiling the served tree's took 30 to 45 s of every
run's set-up on the chip (3.7 s where the seed's own program was cached;
PERF.md Findings, PR 23).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """``--seed`` as two int32 words (it may pass 2**31), to hand to a
    jitted program as its argument."""
    return np.array([seed & 0x7FFFFFFF, seed >> 31], np.int32)


def root_key(seed) -> jax.Array:
    """``seed`` is ``seed_words(--seed)``, traced or not (or the plain
    whole number, outside a jitted program).  The key is
    of the ``rbg`` kind: its bits come from the chip's own generator, which
    makes the 3.75 G values of the served tree in seconds where threefry
    took 42 s (PERF.md Findings, PR 23); a leaf's bits depend on its key and
    shape alone, so the reference makes the same leaf in another program."""
    if isinstance(seed, int):
        seed = seed_words(seed)
    return jax.random.fold_in(jax.random.key(seed[0], impl="rbg"), seed[1])


def leaf_key(seed, layer, name: str) -> jax.Array:
    """``layer`` is -1 for what belongs to no layer."""
    key = jax.random.fold_in(root_key(seed), layer + 1)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _normal(key, shape, std, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def llm_layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], float, float]]:
    """Leaf name -> (shape, std, mean) of one decoder layer, in the layout of
    models/transformer.py (flax DenseGeneral kernels)."""
    h, nh, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = h // nh, cfg["intermediate_size"]
    return {
        "attn/query": ((h, nh, hd), h ** -0.5, 0.0),
        "attn/key": ((h, kv, hd), h ** -0.5, 0.0),
        "attn/value": ((h, kv, hd), h ** -0.5, 0.0),
        "attn/out": ((nh, hd, h), h ** -0.5, 0.0),
        "mlp/gate": ((h, ff), h ** -0.5, 0.0),
        "mlp/up": ((h, ff), h ** -0.5, 0.0),
        "mlp/down": ((ff, h), ff ** -0.5, 0.0),
        "attn_norm": ((h,), 0.1, 1.0),
        "mlp_norm": ((h,), 0.1, 1.0),
    }


def llm_top_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], float, float]]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": ((v, h), 1.0, 0.0),
        "final_norm": ((h,), 0.1, 1.0),
        "lm_head": ((h, v), h ** -0.5, 0.0),
    }


def llm_layer(cfg: dict, seed, layer) -> dict[str, jax.Array]:
    return {
        name: _normal(leaf_key(seed, layer, name), shape, std, mean)
        for name, (shape, std, mean) in llm_layer_shapes(cfg).items()
    }


def llm_top(cfg: dict, seed) -> dict[str, jax.Array]:
    return {
        name: _normal(leaf_key(seed, -1, name), shape, std, mean)
        for name, (shape, std, mean) in llm_top_shapes(cfg).items()
    }


def llm_params_tree(cfg: dict, seed) -> dict:
    """The whole tree in the program's own layout (``TransformerLM`` params).
    Call under ``jax.jit`` with ``seed`` (``seed_words``) as the argument:
    one program, the same for every seed, makes every leaf on the device."""
    top = llm_top(cfg, seed)
    tree = {
        "embed": {"embedding": top["embed"]},
        "final_norm": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["lm_head"]},
    }
    for i in range(cfg["num_hidden_layers"]):
        leaf = llm_layer(cfg, seed, i)
        tree[f"layer_{i}"] = {
            "attn": {n: {"kernel": leaf[f"attn/{n}"]} for n in ("query", "key", "value", "out")},
            "mlp": {n: {"kernel": leaf[f"mlp/{n}"]} for n in ("gate", "up", "down")},
            "attn_norm": {"scale": leaf["attn_norm"]},
            "mlp_norm": {"scale": leaf["mlp_norm"]},
        }
    return tree
