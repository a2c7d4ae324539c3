"""Plain reference of the served decoder (Mistral-7B-v0.1's block: RMSNorm,
grouped-query attention with rotary embeddings and a sliding window,
SwiGLU), teacher-forced over prompt + served tokens, one layer at a time
with the layer's weights regenerated from the seed (chipbench/weights.py).

Departures from the published description, each because the program does
the same and weights are random: rotary pairs are (2i, 2i+1) and not
(i, i + d/2) (a fixed permutation of the query/key columns); RMSNorm's
epsilon is 1e-6, not 1e-5.

``quant="w8a8"`` is the control: the same arithmetic with every matmul's
weights rounded to int8 per output channel and its input rounded to int8
per token, the nearest precision below bfloat16 and the step the program's
own ``cfg.quant`` would take.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from .. import weights

EPS = 1e-6


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _matmul(x, w, quant):
    """x [..., in] @ w [in, out] in float32."""
    if quant == "w8a8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _rope(x, positions, theta):
    """x [seq, heads, head_dim]; rotate pairs (2i, 2i+1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def layer_forward(cfg: dict, w: dict, h, quant=None):
    """One decoder layer on one sequence h [seq, hidden] (float32)."""
    seq = h.shape[0]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(seq)
    x = _rmsnorm(h, w["attn_norm"])
    q = _matmul(x, w["attn/query"].reshape(-1, nh * hd), quant).reshape(seq, nh, hd)
    k = _matmul(x, w["attn/key"].reshape(-1, kv * hd), quant).reshape(seq, kv, hd)
    v = _matmul(x, w["attn/value"].reshape(-1, kv * hd), quant).reshape(seq, kv, hd)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, nh // kv, axis=1), jnp.repeat(v, nh // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * hd ** -0.5
    mask = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        mask &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision="highest").reshape(seq, nh * hd)
    h = h + _matmul(a, w["attn/out"].reshape(nh * hd, -1), quant)
    x = _rmsnorm(h, w["mlp_norm"])
    gate, up = _matmul(x, w["mlp/gate"], quant), _matmul(x, w["mlp/up"], quant)
    return h + _matmul(jax.nn.silu(gate) * up, w["mlp/down"], quant)


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str):
    """The jitted pieces, once per configuration; the seed is their argument."""
    cfg = json.loads(cfg_json)
    return (
        jax.jit(lambda words, i: weights.llm_layer(cfg, words, i)),
        jax.jit(lambda words: weights.llm_top(cfg, words)),
        {q: jax.jit(functools.partial(layer_forward, cfg, quant=q)) for q in (None, "w8a8")},
    )


def forward_logits(cfg: dict, seed: int, ids, quants=(None,)):
    """Float32 logits [len(quants), n_seq, seq, vocab-row-block...] is too
    large to keep: returns, for each ``quant``, the hidden states after the
    final norm, [n_seq, seq, hidden]; ``logit_rows`` turns rows into logits.

    ``ids`` [n_seq, seq] int32, every sequence padded to the same length
    (causal: padding past a sequence's end changes nothing before it).
    """
    gen_layer, gen_top, layer = _programs(json.dumps(cfg, sort_keys=True))
    words = weights.seed_words(seed)
    top = gen_top(words)
    hs = {q: [top["embed"][row].astype(jnp.float32) for row in ids] for q in quants}
    for i in range(cfg["num_hidden_layers"]):
        w = gen_layer(words, jnp.int32(i))
        for q in quants:
            hs[q] = [layer[q](w, h) for h in hs[q]]
        del w
    final = top["final_norm"].astype(jnp.float32)
    return {q: [_rmsnorm(h, final) for h in hs[q]] for q in quants}, top["lm_head"]


@functools.partial(jax.jit, static_argnames=("quant",))
def logit_rows(h_rows, lm_head, quant=None):
    """Logits of some rows: h_rows [rows, hidden] -> [rows, vocab]."""
    return _matmul(h_rows, lm_head.astype(jnp.float32), quant)


def served_gaps(cfg: dict, seed: int, cases: list[dict], pad_to: int, control: bool = False) -> list[dict]:
    """For each case ``{"prompt": [...], "tokens": [...]}``: at every served
    position the gap by which the served token's reference logit lies below
    the reference's best; with ``control`` also the gap of the token the
    w8a8 control puts first there."""
    import numpy as np

    ids = np.zeros((len(cases), pad_to), np.int32)
    for r, c in enumerate(cases):
        seq = list(c["prompt"]) + list(c["tokens"])
        ids[r, : len(seq)] = seq
    quants = (None, "w8a8") if control else (None,)
    hidden, lm_head = forward_logits(cfg, seed, jnp.asarray(ids), quants)
    out = []
    for r, c in enumerate(cases):
        lo, n = len(c["prompt"]) - 1, len(c["tokens"])
        ref = np.asarray(logit_rows(hidden[None][r][lo : lo + n], lm_head))
        served = np.asarray(c["tokens"])
        gap = ref.max(axis=-1) - ref[np.arange(n), served]
        row = {"gaps": gap.tolist(), "ref_argmax": ref.argmax(axis=-1).tolist()}
        if control:
            ctl = np.asarray(logit_rows(hidden["w8a8"][r][lo : lo + n], lm_head, quant="w8a8"))
            pick = ctl.argmax(axis=-1)
            row["control_gaps"] = (ref.max(axis=-1) - ref[np.arange(n), pick]).tolist()
        out.append(row)
    return out
