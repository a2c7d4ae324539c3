"""Plain reference of the tests' second family: ``reference/llm.py``'s
block over leaves of its own.  Every leaf is drawn under a key name of this
family (``toy_mixed/...``), and a layer's matrices with a std that depends
on the LAYER'S INDEX, so ``reference/llm.py``, which draws one leaf set for
every layer under its own names, regenerates other weights and cannot judge
what this family serves."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import llm


def layer_scale(layer: int) -> float:
    """What a layer's matrices are scaled by: 1, 1/2, 1/3, ..."""
    return 1.0 / (1 + layer)


def _draw(seed, layer: int, name: str, shape, std: float, mean: float):
    key = weights.leaf_key(seed, layer, f"toy_mixed/{name}")
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def layer_leaves(cfg: dict, seed, layer: int) -> dict:
    """``layer`` is a Python whole number: the leaf set depends on it."""
    return {
        name: _draw(seed, layer, name, shape, std if mean else std * layer_scale(layer), mean)
        for name, (shape, std, mean) in weights.llm_layer_shapes(cfg).items()
    }


def top_leaves(cfg: dict, seed) -> dict:
    return {name: _draw(seed, -1, name, *spec) for name, spec in weights.llm_top_shapes(cfg).items()}


def served_gaps(cfg: dict, seed: int, cases: list[dict], pad_to: int, control: bool = False) -> list[dict]:
    """Rows as ``reference/llm.py::served_gaps`` returns them."""
    words = weights.seed_words(seed)
    quants = (None, "w8a8") if control else (None,)
    ids = np.zeros((len(cases), pad_to), np.int32)
    for r, c in enumerate(cases):
        seq = list(c["prompt"]) + list(c["tokens"])
        ids[r, : len(seq)] = seq
    top = jax.jit(lambda w: top_leaves(cfg, w))(words)
    hs = {q: [top["embed"][row].astype(jnp.float32) for row in ids] for q in quants}
    forward = {q: jax.jit(functools.partial(llm.layer_forward, cfg, quant=q)) for q in quants}
    for i in range(cfg["num_hidden_layers"]):
        w = jax.jit(lambda w, i=i: layer_leaves(cfg, w, i))(words)
        for q in quants:
            hs[q] = [forward[q](w, h) for h in hs[q]]
    final = top["final_norm"].astype(jnp.float32)
    out = []
    for r, c in enumerate(cases):
        lo, n = len(c["prompt"]) - 1, len(c["tokens"])
        rows = {q: llm._rmsnorm(hs[q][r], final)[lo : lo + n] for q in quants}
        ref = np.asarray(llm.logit_rows(rows[None], top["lm_head"]))
        best = ref.max(axis=-1)
        row = {"gaps": (best - ref[np.arange(n), np.asarray(c["tokens"])]).tolist(),
               "ref_argmax": ref.argmax(axis=-1).tolist()}
        if control:
            pick = np.asarray(llm.logit_rows(rows["w8a8"], top["lm_head"], quant="w8a8")).argmax(axis=-1)
            row["control_gaps"] = (best - ref[np.arange(n), pick]).tolist()
        out.append(row)
    return out
