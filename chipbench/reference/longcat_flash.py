"""Plain reference of the LongCat-Flash decoder (meituan-longcat/LongCat-Flash-Chat:
shortcut-connected double-layers of two latent attentions, two dense SwiGLUs
and ONE expert layer with zero-computation experts), teacher-forced over
prompt + served tokens.  Float32 at ``precision="highest"``, the EXPANDED form
of latent attention only (per-head keys and values through ``W_kvb``), a full
causal forward: no cache, no absorbed form, no batching, no gather.  One
double-layer at a time with its leaves made again from the seed.  Shares no
code with the program (``k8s_device_plugin_tpu/``).

The equations (``h`` hidden, ``H`` heads, ranks ``r_q`` / ``r_kv``, head
widths ``d_n`` / ``d_r`` / ``d_v``, ``E`` routed and ``Z`` zero-computation
experts, ``k`` chosen, scaling ``s``; every linear without bias; ``norm`` is
RMSNorm with ``rms_norm_eps``)::

    A_j(x):  c_q = norm(x W_qa) * sqrt(h / r_q)         [q_n | q_r] = c_q W_qb   per head
             [c | k_r] = x W_kva;   c^ = norm(c) * sqrt(h / r_kv)   (k_r is not scaled)
             [k_n | v] = c^ W_kvb   per head;   rope on q_r (every head) and k_r (one key for all)
             score = (q_n.k_n + q_r.k_r) / sqrt(d_n + d_r), causal softmax, o = sum p v, out = concat(o) W_o
    M(u):    p = softmax(float32(u) float32(W_r))       E + Z wide
             chosen = the k largest of p + b            b (e_score_correction_bias) biases the CHOICE only
             w_i = s p_i, not renormalised
             M = sum_{i < E chosen} w_i down_i(silu(gate_i u) * up_i u) + sum_{i >= E chosen} w_i u
    layer:   a = x + A_0(norm_0a x);  u = norm_0b a;  m = M(u);  y = a + F_0(u)
             z = y + A_1(norm_1a y);  out = z + F_1(norm_1b z) + m
    logits = W_head norm_final(x_after_all_layers)      untied head

**The chip's share.**  ``held`` names the routed experts whose part of ``M``
is computed (the configuration's ``deployment.held_experts``); every chosen
zero-computation expert is computed too; what the other routed experts would
add is left out, and that partial ``M`` goes on.  ``held`` = all ``E`` is the
uncut layer (tests/test_moe.py adds the shares of all ranks up to it).

Departure, as ``reference/llm.py``: rotary pairs are (2i, 2i+1), which is
also the published interleaved form.  ``mla_scale_*_lora`` true is read as
the square roots above; ``norm_topk_prob`` false; ``router_bias`` false;
``zero_expert_type`` identity.

**Seeded leaves** (``leaf_stds``): normal, bfloat16.  Every branch adds a
share to the residual, and a flipped routing choice must stay a small thing:
an expert chosen twelfth by a hair in float32 and thirteenth in bfloat16 is
the architecture's own discontinuity, and with every branch at 0.7 of the
stream such flips cascade through the later routers until single logits
differ by 1 to 2 (read on the chip: ``gap_max`` 1.28 sound, 2.10 under the
int8 control; no limit lies between).  So: queries come out at size 1.5 (a
softmax over 900 positions rests on about a hundred of them; ``o`` scales
the output to 0.7), keys, values, gates and ups at size 1, each dense SwiGLU
adds 0.7; ``norm_0b``, whose output ``u`` is what a zero-computation expert
adds, has scales near ``EXPERT_INPUT_SCALE`` = 0.3, and what reads ``u`` (the
first SwiGLU, the router, the experts' gates and ups) is scaled back up by
1/0.3; router logits have std 2 (the chosen twelve hold 0.4 of the mass,
``s p_i`` between 0.7 and 0.085), so the four identity experts a token meets
on average add 0.25, a chosen held expert (output size 1.5) about as much,
and one flip at the boundary moves the stream by a hundredth.  ``b`` is
normal with std 0.003, three times the gap between the twelfth and the
thirteenth probability, so it changes the choice for most tokens.  The
head's std gives logits of std 2.

``quant="w8a8"`` is the control: every bfloat16 matmul's weights rounded to
int8 per output channel and its input to int8 per token (the router stays
float32: the configuration states it so).  ``drop`` leaves a part out
(``"experts"``: m = 0; ``"attention_1"``: the pair's second attention): the
proof that the comparison sees it.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

ATTN = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")


# ------------------------------------------------------------------ leaves


def dims(cfg: dict) -> dict:
    return {
        "h": cfg["hidden_size"], "H": cfg["num_attention_heads"], "r_q": cfg["q_lora_rank"],
        "r_kv": cfg["kv_lora_rank"], "d_n": cfg["qk_nope_head_dim"], "d_r": cfg["qk_rope_head_dim"],
        "d_v": cfg["v_head_dim"], "ff": cfg["ffn_hidden_size"], "f": cfg["expert_ffn_hidden_size"],
        "E": cfg["published"]["n_routed_experts"], "Z": cfg["zero_expert_num"], "k": cfg["moe_topk"],
        "s": float(cfg["routed_scaling_factor"]),
        "scale_q": math.sqrt(cfg["hidden_size"] / cfg["q_lora_rank"]) if cfg["mla_scale_q_lora"] else 1.0,
        "scale_kv": math.sqrt(cfg["hidden_size"] / cfg["kv_lora_rank"]) if cfg["mla_scale_kv_lora"] else 1.0,
    }


def held_experts(cfg: dict) -> tuple[int, ...]:
    held = tuple(cfg["deployment"]["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {cfg['n_routed_experts']} counts the held experts, deployment holds {held}")
    return held


# Mean scale of the norm that feeds the expert layer and the first dense
# SwiGLU (norm_0b): its output u is what a zero-computation expert adds.
EXPERT_INPUT_SCALE = 0.3


def leaf_stds(cfg: dict) -> dict[str, float]:
    """The seeded std of every matrix leaf (module docstring, Seeded leaves)."""
    d = dims(cfg)
    h, u = d["h"], EXPERT_INPUT_SCALE
    return {
        "embed": 1.0,
        "q_a": 1.0 / math.sqrt(h),
        "q_b": 1.5 / (d["scale_q"] * math.sqrt(d["r_q"])),
        "kv_a": 1.0 / math.sqrt(h),
        "kv_b": 1.0 / (d["scale_kv"] * math.sqrt(d["r_kv"])),
        "o": 0.7 / (0.12 * math.sqrt(d["H"] * d["d_v"])),
        "mlp/gate": 1.0 / math.sqrt(h), "mlp/up": 1.0 / math.sqrt(h),
        # silu(gate) * up of sizes 1 has a size near 0.5
        "mlp/down": 0.7 / (0.5 * math.sqrt(d["ff"])),
        # What reads u (size EXPERT_INPUT_SCALE) is scaled back up to size 1.
        "mlp0/gate": 1.0 / (u * math.sqrt(h)), "mlp0/up": 1.0 / (u * math.sqrt(h)),
        "moe/router": 2.0 / (u * math.sqrt(h)),
        "moe/bias": 0.003,
        "moe/gate": 1.0 / (u * math.sqrt(h)), "moe/up": 1.0 / (u * math.sqrt(h)),
        "moe/down": 1.5 / (0.5 * math.sqrt(d["f"])),
        "lm_head": 2.0 / math.sqrt(h),
    }


def _normal(key, shape, std, mean=0.0, dtype=jnp.bfloat16):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def expert_leaves(cfg: dict, seed, layer, expert) -> dict[str, jax.Array]:
    """Routed expert ``expert`` (its published index) of double-layer
    ``layer``: the same bits whichever experts are made beside it."""
    d, std = dims(cfg), leaf_stds(cfg)
    key = lambda name: jax.random.fold_in(weights.leaf_key(seed, layer, name), expert)  # noqa: E731
    return {
        "gate": _normal(key("moe/gate"), (d["h"], d["f"]), std["moe/gate"]),
        "up": _normal(key("moe/up"), (d["h"], d["f"]), std["moe/up"]),
        "down": _normal(key("moe/down"), (d["f"], d["h"]), std["moe/down"]),
    }


def layer_leaves(cfg: dict, seed, layer, held) -> dict[str, jax.Array]:
    """One double-layer's leaves under the reference's own names, each from
    a key of its own (seed, layer, name).  ``moe/experts_*`` are stacked
    over ``held``, in its order."""
    d, std = dims(cfg), leaf_stds(cfg)
    key = lambda name: weights.leaf_key(seed, layer, name)  # noqa: E731
    h = d["h"]
    out = {}
    for j in (0, 1):
        shapes = {
            "q_a": (h, d["r_q"]), "q_b": (d["r_q"], d["H"] * (d["d_n"] + d["d_r"])),
            "kv_a": (h, d["r_kv"] + d["d_r"]), "kv_b": (d["r_kv"], d["H"] * (d["d_n"] + d["d_v"])),
            "o": (d["H"] * d["d_v"], h),
        }
        for name, shape in shapes.items():
            out[f"attn{j}/{name}"] = _normal(key(f"attn{j}/{name}"), shape, std[name])
        for name, width in (("q_norm", d["r_q"]), ("kv_norm", d["r_kv"])):
            out[f"attn{j}/{name}"] = _normal(key(f"attn{j}/{name}"), (width,), 0.1, 1.0)
        for name, shape in (("gate", (h, d["ff"])), ("up", (h, d["ff"])), ("down", (d["ff"], h))):
            out[f"mlp{j}/{name}"] = _normal(key(f"mlp{j}/{name}"), shape, std.get(f"mlp{j}/{name}", std[f"mlp/{name}"]))
        for name in (f"norm{j}a", f"norm{j}b"):
            mean = EXPERT_INPUT_SCALE if name == "norm0b" else 1.0
            out[name] = _normal(key(name), (h,), 0.1 * mean, mean)
    out["moe/router"] = _normal(key("moe/router"), (h, d["E"] + d["Z"]), std["moe/router"])
    out["moe/bias"] = _normal(key("moe/bias"), (d["E"] + d["Z"],), std["moe/bias"], dtype=jnp.float32)
    # One loop body for all held experts (unrolled, the served tree's
    # program took a minute to compile for the chip).
    experts = jax.lax.map(lambda e: expert_leaves(cfg, seed, layer, e), jnp.asarray(held, jnp.int32))
    for name in ("gate", "up", "down"):
        out[f"moe/experts_{name}"] = experts[name]
    return out


def top_leaves(cfg: dict, seed) -> dict[str, jax.Array]:
    h, v, std = cfg["hidden_size"], cfg["vocab_size"], leaf_stds(cfg)
    key = lambda name: weights.leaf_key(seed, -1, name)  # noqa: E731
    return {
        "embed": _normal(key("embed"), (v, h), std["embed"]),
        "final_norm": _normal(key("final_norm"), (h,), 0.1, 1.0),
        "lm_head": _normal(key("lm_head"), (h, v), std["lm_head"]),
    }


# ----------------------------------------------------------------- forward


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _matmul(x, w, quant=None):
    """x [..., in] @ w [in, out] in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "w8a8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [seq, heads, width]; rotate pairs (2i, 2i+1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def attention(cfg: dict, w: dict, prefix: str, x, quant=None):
    """Latent attention ``prefix`` ("attn0" / "attn1") on one sequence x
    [seq, hidden], expanded form."""
    d, eps, theta = dims(cfg), cfg["rms_norm_eps"], float(cfg["rope_theta"])
    seq, heads = x.shape[0], d["H"]
    pos = jnp.arange(seq)
    c_q = _rmsnorm(_matmul(x, w[f"{prefix}/q_a"], quant), w[f"{prefix}/q_norm"], eps) * d["scale_q"]
    q = _matmul(c_q, w[f"{prefix}/q_b"], quant).reshape(seq, heads, d["d_n"] + d["d_r"])
    kv = _matmul(x, w[f"{prefix}/kv_a"], quant)
    latent = _rmsnorm(kv[:, : d["r_kv"]], w[f"{prefix}/kv_norm"], eps) * d["scale_kv"]
    kvh = _matmul(latent, w[f"{prefix}/kv_b"], quant).reshape(seq, heads, d["d_n"] + d["d_v"])
    q_r = _rope(q[..., d["d_n"]:], pos, theta)
    k_r = _rope(kv[:, None, d["r_kv"]:], pos, theta)  # [seq, 1, d_r]: one key for all heads
    s = jnp.einsum("qhd,khd->hqk", q[..., : d["d_n"]], kvh[..., : d["d_n"]], precision="highest")
    s = s + jnp.einsum("qhd,kd->hqk", q_r, k_r[:, 0], precision="highest")
    s = s / math.sqrt(d["d_n"] + d["d_r"])
    p = jax.nn.softmax(jnp.where((pos[None, :] <= pos[:, None])[None], s, -1e30), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kvh[..., d["d_n"]:], precision="highest").reshape(seq, heads * d["d_v"])
    return _matmul(o, w[f"{prefix}/o"], quant)


def swiglu(gate, up, down, x, quant=None):
    return _matmul(jax.nn.silu(_matmul(x, gate, quant)) * _matmul(x, up, quant), down, quant)


def route(cfg: dict, w: dict, u, use_bias: bool = True):
    """(ids [seq, k] of the chosen experts, weights [seq, k])."""
    d = dims(cfg)
    p = jax.nn.softmax(jnp.matmul(u, w["moe/router"].astype(jnp.float32), precision="highest"), axis=-1)
    _, ids = jax.lax.top_k(p + w["moe/bias"] if use_bias else p, d["k"])
    return ids, jnp.take_along_axis(p, ids, axis=-1) * d["s"]


def expert_layer(cfg: dict, w: dict, u, held, quant=None, use_bias: bool = True, identity: bool = True):
    """The share of M(u) that ``held`` (the routed experts whose leaves
    ``w`` stacks, in this order) and, with ``identity``, the
    zero-computation experts give, on one sequence u [seq, hidden]."""
    d = dims(cfg)
    ids, wt = route(cfg, w, u, use_bias)
    out = jnp.zeros_like(u)
    for local, expert in enumerate(held):
        share = jnp.sum(jnp.where(ids == expert, wt, 0.0), axis=-1)  # [seq]
        part = swiglu(w["moe/experts_gate"][local], w["moe/experts_up"][local], w["moe/experts_down"][local], u, quant)
        out = out + share[:, None] * part
    if identity:
        out = out + jnp.sum(jnp.where(ids >= d["E"], wt, 0.0), axis=-1)[:, None] * u
    return out


def layer_forward(cfg: dict, w: dict, x, held, quant=None, drop=None):
    """One double-layer on one sequence x [seq, hidden] (float32)."""
    eps = cfg["rms_norm_eps"]
    a = x + attention(cfg, w, "attn0", _rmsnorm(x, w["norm0a"], eps), quant)
    u = _rmsnorm(a, w["norm0b"], eps)
    m = 0.0 if drop == "experts" else expert_layer(cfg, w, u, held, quant)
    y = a + swiglu(w["mlp0/gate"], w["mlp0/up"], w["mlp0/down"], u, quant)
    z = y if drop == "attention_1" else y + attention(cfg, w, "attn1", _rmsnorm(y, w["norm1a"], eps), quant)
    v = _rmsnorm(z, w["norm1b"], eps)
    return z + swiglu(w["mlp1/gate"], w["mlp1/up"], w["mlp1/down"], v, quant) + m


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, drop):
    """The jitted pieces, once per configuration; the seed is their argument."""
    cfg = json.loads(cfg_json)
    held = held_experts(cfg)
    return {
        "layer": jax.jit(lambda words, i: layer_leaves(cfg, words, i, held)),
        "top": jax.jit(lambda words: top_leaves(cfg, words)),
        "forward": {q: jax.jit(functools.partial(layer_forward, cfg, held=held, quant=q, drop=drop))
                    for q in (None, "w8a8")},
    }


def forward_hidden(cfg: dict, seed: int, ids: np.ndarray, quants=(None,), drop=None):
    """For each ``quant``: the hidden states after the final norm, a list of
    [seq, hidden] per sequence, and the head.  ``ids`` [n_seq, seq] int32,
    every sequence padded to the same length (causal: padding past a
    sequence's end changes nothing before it)."""
    prog = _programs(json.dumps(cfg, sort_keys=True), drop)
    words = weights.seed_words(seed)
    top = prog["top"](words)
    x = np.asarray(top["embed"].astype(jnp.float32))[ids]
    hs = {q: [jnp.asarray(row) for row in x] for q in quants}
    for i in range(cfg["num_layers"]):
        w = prog["layer"](words, jnp.int32(i))
        for q in quants:
            hs[q] = [prog["forward"][q](w, h) for h in hs[q]]
        del w
    final = top["final_norm"]
    return {q: [_rmsnorm(h, final, cfg["rms_norm_eps"]) for h in hs[q]] for q in quants}, top["lm_head"]


def served_gaps(cfg: dict, seed: int, cases: list[dict], pad_to: int, control: bool = False,
                drop=None) -> list[dict]:
    """For each case ``{"prompt": [...], "tokens": [...]}``: at every served
    position the gap by which the served token's reference logit lies below
    the reference's best; with ``control`` also the gap of the token the
    w8a8 control puts first there."""
    ids = np.zeros((len(cases), pad_to), np.int32)
    for r, c in enumerate(cases):
        seq = list(c["prompt"]) + list(c["tokens"])
        ids[r, : len(seq)] = seq
    quants = (None, "w8a8") if control else (None,)
    hidden, head = forward_hidden(cfg, seed, ids, quants, drop)
    logits = jax.jit(_matmul, static_argnames=("quant",))
    out = []
    for r, c in enumerate(cases):
        lo, n = len(c["prompt"]) - 1, len(c["tokens"])
        ref = np.asarray(logits(hidden[None][r][lo : lo + n], head))
        served = np.asarray(c["tokens"])
        gap = ref.max(axis=-1) - ref[np.arange(n), served]
        row = {"gaps": gap.tolist(), "ref_argmax": ref.argmax(axis=-1).tolist()}
        if control:
            pick = np.asarray(logits(hidden["w8a8"][r][lo : lo + n], head, quant="w8a8")).argmax(axis=-1)
            row["control_gaps"] = (ref.max(axis=-1) - ref[np.arange(n), pick]).tolist()
        out.append(row)
    return out
