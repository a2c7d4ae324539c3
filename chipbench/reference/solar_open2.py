"""Plain reference of the Solar Open 2 decoder (upstage/Solar-Open2-250B:
gated delta-rule linear-attention layers beside one gated softmax GQA
layer without positions in four, and in EVERY layer an expert layer with
one shared expert), teacher-forced over prompt + served tokens.  Float32
at ``precision="highest"``, the linear attention in its RECURRENT form (a
``lax.scan`` a token at a time), a full causal forward: no cache, no
chunks, no batching, no kernel.  One layer at a time with its leaves made
again from the seed.  Shares no code with the program
(``k8s_device_plugin_tpu/``).

The equations (``h`` hidden; ``H`` query heads and ``G`` key-value heads of
``d``; the linear attention's ``H_l`` heads of ``d_l``, low rank ``r``;
``E`` routed experts, ``k`` chosen; every linear without bias; ``norm`` is
RMSNorm with ``rms_norm_eps``; ``conv_*`` a causal depthwise convolution of
``short_conv_kernel_size`` taps, zeros before the first token)::

    K(x):  q_t = l2norm(silu(conv_q(x W_q)));  k_t = l2norm(silu(conv_k(x W_k)));  v_t = silu(conv_v(x W_v))
           g_t = -exp(A_log) softplus((x W_fa) W_fb + dt_bias);   beta_t = 2 sigmoid(x W_b)
           S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0   (per head)
           o_t = S_t^T q_t / sqrt(d_l);   K = concat_heads(norm_d(o_t) w_o sigmoid((x W_ga) W_gb)) W_o
    A(x):  q = x W_q, k = x W_k, v = x W_v (query head j reads key-value head j // (H / G)), no rotary
           o = causal softmax(q.k / sqrt(d)) v;   A = (o * sigmoid(x W_gate)) W_o
    M(u):  s = sigmoid(float32(u) float32(W_r));   chosen = the k largest of s + b   (b biases the CHOICE only)
           w_i = s_i / sum_chosen s_j * routed_scaling_factor
           M = F_shared(u) + sum_chosen w_i F_i(u),   F(u) = (silu(u G) * u U) D
    layer l: a = x + X_l(norm_la x)  (X = A in ``gqa_layers``, K elsewhere);   out = a + M(norm_lb a)
    logits = W_head norm_final(x_after_all_layers)      untied head

``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``; the update is applied as
``Diag(exp(g)) S - beta k (k^T Diag(exp(g)) S) + beta k v^T``, the same
product without a d x d matrix a token.

**The chip's share.**  ``held`` names the routed experts whose part of ``M``
is computed (the configuration's ``deployment.held_experts``); the shared
expert is computed where the token is, on every chip alike; what the
other routed experts would add is left out, and that partial ``M`` goes on.
``held`` = all ``E`` is the uncut layer (tests/test_solar_open2.py adds the
shares of all ranks, the shared expert once, up to it).

**Seeded leaves** (``leaf_stds``): normal, bfloat16, but ``A_log``,
``dt_bias`` (float32, Mamba's and Kimi's initialisation: ``A`` uniform in
[1, 16], ``dt`` log-uniform in [0.001, 0.1], so some channels remember
hundreds of tokens and others a few) and the selection bias.  The softmax
attention, the linear attention and the shared expert each add about 0.7
to a residual of entries near 1, the logits have std 2.  A routed
expert's output is small (``ROUTED_OUT`` = 0.15): sigmoid scores
renormalised give the eighth choice the weight of the first, about 1/8, and
bfloat16 rounding flips the eighth choice of some tokens in every layer; at
0.15 a flip moves the stream by about 0.02, so flips do not cascade.  The
router's logits have std 2; ``b`` is normal with std ``bias_std``, the
expected gap between the k-th and the (k+1)-th largest score (0.002 at 8
of 320): it changes the choice for a visible share of tokens and moves an
expert's load by about a tenth, as a trained bias leaves it near even.

``quant="w8a8"`` is the control: every bfloat16 matmul's weights rounded to
int8 per output channel and its input to int8 per token (the router stays
float32).  ``drop`` leaves a part out (``"recurrence"``: the linear
attention forgets every token, ``S_{t-1}`` = 0; ``"attention"``: the softmax
attention layers add nothing): the proof that the comparison sees it.
"""

from __future__ import annotations

import functools
import json
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

ROUTED_OUT = 0.15
ROUTER_STD = 2.0  # of the router's logits
L2_EPS = 1e-6
EXPERT = ("gate", "up", "down")


# ------------------------------------------------------------------ leaves


def dims(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return {
        "h": cfg["hidden_size"], "H": cfg["num_attention_heads"], "G": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "H_l": lin["num_heads"], "d_l": lin["head_dim"], "conv": lin["short_conv_kernel_size"],
        "r": lin["head_dim"],  # kda_use_full_proj false: the decay and gate projections are of rank head_dim
        "E": cfg["published"]["n_routed_experts"], "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "f_s": cfg["n_shared_experts"] * cfg["moe_intermediate_size"], "s": float(cfg["routed_scaling_factor"]),
        "eps": cfg["rms_norm_eps"],
    }


def held_experts(cfg: dict) -> tuple[int, ...]:
    held = tuple(cfg["deployment"]["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {cfg['n_routed_experts']} counts the held experts, deployment holds {held}")
    return held


def attention_layers(cfg: dict) -> tuple[int, ...]:
    """The softmax layers among the layers kept (``gqa_layers`` as published)."""
    return tuple(i for i in cfg["gqa_layers"] if i < cfg["num_hidden_layers"])


def bias_std(cfg: dict) -> float:
    """The expected gap between the k-th and the (k+1)-th largest of E
    sigmoid scores whose logits are normal with std ``ROUTER_STD``: the
    spacing of the order statistics there, ROUTER_STD / (E pdf(z_k)), times
    the sigmoid's slope at z_k."""
    d, unit = dims(cfg), statistics.NormalDist()
    z = unit.inv_cdf(1.0 - d["k"] / d["E"])
    s = 1.0 / (1.0 + math.exp(-ROUTER_STD * z))
    return ROUTER_STD / (d["E"] * unit.pdf(z)) * s * (1.0 - s)


def leaf_stds(cfg: dict) -> dict[str, float]:
    """The seeded std of every bfloat16 matrix leaf (module docstring)."""
    d = dims(cfg)
    h = d["h"]
    return {
        "embed": 1.0,
        # Queries of size 1.5: a softmax over hundreds of positions rests on fewer.
        "attn/q": 1.5 / math.sqrt(h), "attn/k": 1.0 / math.sqrt(h), "attn/v": 1.0 / math.sqrt(h),
        "attn/gate": 1.0 / math.sqrt(h),
        # o averages values of size 1 over many positions (about 0.15), the gate halves it.
        "attn/o": 0.7 / (0.075 * math.sqrt(d["H"] * d["d"])),
        "kda/q": 1.0 / math.sqrt(h), "kda/k": 1.0 / math.sqrt(h), "kda/v": 1.0 / math.sqrt(h),
        "kda/conv": 1.0 / math.sqrt(d["conv"]),
        "kda/f_a": 1.0 / math.sqrt(h), "kda/f_b": 1.0 / math.sqrt(d["r"]), "kda/b": 1.0 / math.sqrt(h),
        "kda/g_a": 1.0 / math.sqrt(h), "kda/g_b": 1.0 / math.sqrt(d["r"]),
        # norm_d(o) has size 1, the gate about 0.5.
        "kda/o": 0.7 / (0.5 * math.sqrt(d["H_l"] * d["d_l"])),
        "moe/router": ROUTER_STD / math.sqrt(h),
        "moe/gate": 1.0 / math.sqrt(h), "moe/up": 1.0 / math.sqrt(h),
        # silu(gate) * up of sizes 1 has a size near 0.5.
        "moe/down": ROUTED_OUT / (0.5 * math.sqrt(d["f"])),
        "moe/shared_gate": 1.0 / math.sqrt(h), "moe/shared_up": 1.0 / math.sqrt(h),
        "moe/shared_down": 0.7 / (0.5 * math.sqrt(d["f_s"])),
        "lm_head": 2.0 / math.sqrt(h),
    }


def _normal(key, shape, std, mean=0.0, dtype=jnp.bfloat16):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def expert_leaves(cfg: dict, seed, layer, expert) -> dict[str, jax.Array]:
    """Routed expert ``expert`` (its published index) of layer ``layer``:
    the same bits whichever experts are made beside it."""
    d, std = dims(cfg), leaf_stds(cfg)
    key = lambda name: jax.random.fold_in(weights.leaf_key(seed, layer, name), expert)  # noqa: E731
    return {
        "gate": _normal(key("moe/gate"), (d["h"], d["f"]), std["moe/gate"]),
        "up": _normal(key("moe/up"), (d["h"], d["f"]), std["moe/up"]),
        "down": _normal(key("moe/down"), (d["f"], d["h"]), std["moe/down"]),
    }


def layer_leaves(cfg: dict, seed, layer, held) -> dict[str, jax.Array]:
    """Layer ``layer``'s leaves under the reference's own names, each from a
    key of its own (seed, layer, name).  A softmax layer has ``attn/*``, a
    linear one ``kda/*``; ``moe/experts_*`` are stacked over ``held``, in
    its order."""
    d, std = dims(cfg), leaf_stds(cfg)
    key = lambda name: weights.leaf_key(seed, layer, name)  # noqa: E731
    h, out = d["h"], {}
    if layer in attention_layers(cfg):
        qw, kw = d["H"] * d["d"], d["G"] * d["d"]
        for name, shape in (("q", (h, qw)), ("k", (h, kw)), ("v", (h, kw)), ("gate", (h, qw)), ("o", (qw, h))):
            out[f"attn/{name}"] = _normal(key(f"attn/{name}"), shape, std[f"attn/{name}"])
    else:
        w, r = d["H_l"] * d["d_l"], d["r"]
        shapes = {"q": (h, w), "k": (h, w), "v": (h, w), "f_a": (h, r), "f_b": (r, w), "b": (h, d["H_l"]),
                  "g_a": (h, r), "g_b": (r, w), "o": (w, h)}
        for name, shape in shapes.items():
            out[f"kda/{name}"] = _normal(key(f"kda/{name}"), shape, std[f"kda/{name}"])
        for name in ("conv_q", "conv_k", "conv_v"):
            out[f"kda/{name}"] = _normal(key(f"kda/{name}"), (d["conv"], w), std["kda/conv"])
        out["kda/o_norm"] = _normal(key("kda/o_norm"), (d["d_l"],), 0.1, 1.0)
        out["kda/A_log"] = jnp.log(jax.random.uniform(key("kda/A_log"), (d["H_l"],), jnp.float32, 1.0, 16.0))
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key("kda/dt_bias"), (w,), jnp.float32, lo, hi))
        out["kda/dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
    for name in ("norm_a", "norm_b"):
        out[name] = _normal(key(name), (h,), 0.1, 1.0)
    out["moe/router"] = _normal(key("moe/router"), (h, d["E"]), std["moe/router"])
    out["moe/bias"] = _normal(key("moe/bias"), (d["E"],), bias_std(cfg), dtype=jnp.float32)
    for name, shape in (("shared_gate", (h, d["f_s"])), ("shared_up", (h, d["f_s"])), ("shared_down", (d["f_s"], h))):
        out[f"moe/{name}"] = _normal(key(f"moe/{name}"), shape, std[f"moe/{name}"])
    # One loop body for all held experts (unrolled, the program compiles slowly).
    experts = jax.lax.map(lambda e: expert_leaves(cfg, seed, layer, e), jnp.asarray(held, jnp.int32))
    for name in EXPERT:
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


def _causal_conv(x, w):
    """x [seq, C]; w [taps, C], row taps-1 multiplying the current token."""
    taps = w.shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(ext[i : i + x.shape[0]] * w[i].astype(jnp.float32) for i in range(taps))


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_attention(cfg: dict, w: dict, x, quant=None, drop=None):
    """K on one sequence x [seq, hidden]: the recurrence a token at a time."""
    d = dims(cfg)
    seq, heads, dl = x.shape[0], d["H_l"], d["d_l"]

    def branch(name):
        y = jax.nn.silu(_causal_conv(_matmul(x, w[f"kda/{name}"], quant), w[f"kda/conv_{name}"]))
        return y.reshape(seq, heads, dl)

    q, k, v = _l2norm(branch("q")), _l2norm(branch("k")), branch("v")
    decay_in = _matmul(_matmul(x, w["kda/f_a"], quant), w["kda/f_b"], quant) + w["kda/dt_bias"]
    g = (-jnp.exp(w["kda/A_log"])[:, None] * jax.nn.softplus(decay_in).reshape(seq, heads, dl))
    beta = 2.0 * jax.nn.sigmoid(_matmul(x, w["kda/b"], quant)) if cfg["kda_allow_neg_eigval"] else (
        jax.nn.sigmoid(_matmul(x, w["kda/b"], quant)))

    def token(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp  # [H, d] x4, [H]
        if drop == "recurrence":
            s = jnp.zeros_like(s)
        s = s * jnp.exp(g_t)[:, :, None]  # Diag(exp(g)) S
        recall = jnp.einsum("hk,hkv->hv", k_t, s, precision="highest")  # k^T Diag(exp(g)) S
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - recall)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t / math.sqrt(dl), s, precision="highest")

    _, o = jax.lax.scan(token, jnp.zeros((heads, dl, dl), jnp.float32), (q, k, v, g, beta))
    o = _rmsnorm(o, w["kda/o_norm"], d["eps"])
    gate = jax.nn.sigmoid(_matmul(_matmul(x, w["kda/g_a"], quant), w["kda/g_b"], quant)).reshape(seq, heads, dl)
    return _matmul((o * gate).reshape(seq, heads * dl), w["kda/o"], quant)


def attention(cfg: dict, w: dict, x, quant=None):
    """A on one sequence x [seq, hidden]: gated softmax GQA, no positions."""
    d = dims(cfg)
    seq, group = x.shape[0], d["H"] // d["G"]
    pos = jnp.arange(seq)
    q = _matmul(x, w["attn/q"], quant).reshape(seq, d["H"], d["d"])
    k = _matmul(x, w["attn/k"], quant).reshape(seq, d["G"], d["d"])
    v = _matmul(x, w["attn/v"], quant).reshape(seq, d["G"], d["d"])
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # head j reads j // group
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / math.sqrt(d["d"])
    p = jax.nn.softmax(jnp.where((pos[None, :] <= pos[:, None])[None], s, -1e30), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest").reshape(seq, d["H"] * d["d"])
    return _matmul(o * jax.nn.sigmoid(_matmul(x, w["attn/gate"], quant)), w["attn/o"], quant)


def swiglu(gate, up, down, x, quant=None):
    return _matmul(jax.nn.silu(_matmul(x, gate, quant)) * _matmul(x, up, quant), down, quant)


def route(cfg: dict, w: dict, u, use_bias: bool = True):
    """(ids [seq, k] of the chosen experts, weights [seq, k])."""
    d = dims(cfg)
    s = jax.nn.sigmoid(jnp.matmul(u, w["moe/router"].astype(jnp.float32), precision="highest"))
    _, ids = jax.lax.top_k(s + w["moe/bias"] if use_bias else s, d["k"])
    wt = jnp.take_along_axis(s, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    return ids, wt * d["s"]


def expert_layer(cfg: dict, w: dict, u, held, quant=None, use_bias: bool = True, shared: bool = True):
    """The share of M(u) that ``held`` (the routed experts whose leaves
    ``w`` stacks, in this order) and, with ``shared``, the shared expert
    give, on one sequence u [seq, hidden]."""
    ids, wt = route(cfg, w, u, use_bias)
    out = jnp.zeros_like(u)
    if shared:
        out = swiglu(w["moe/shared_gate"], w["moe/shared_up"], w["moe/shared_down"], u, quant)
    for local, expert in enumerate(held):
        share = jnp.sum(jnp.where(ids == expert, wt, 0.0), axis=-1)  # [seq]
        part = swiglu(*(w[f"moe/experts_{n}"][local] for n in EXPERT), u, quant)
        out = out + share[:, None] * part
    return out


def layer_forward(cfg: dict, w: dict, x, softmax: bool, held, quant=None, drop=None):
    """Layer on one sequence x [seq, hidden] (float32)."""
    eps = cfg["rms_norm_eps"]
    xn = _rmsnorm(x, w["norm_a"], eps)
    if softmax:
        a = x if drop == "attention" else x + attention(cfg, w, xn, quant)
    else:
        a = x + linear_attention(cfg, w, xn, quant, drop)
    return a + expert_layer(cfg, w, _rmsnorm(a, w["norm_b"], eps), held, quant)


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, drop):
    """The jitted pieces, once per configuration; the seed is their argument."""
    cfg = json.loads(cfg_json)
    held = held_experts(cfg)
    return {
        "layer": {i: jax.jit(lambda words, i=i: layer_leaves(cfg, words, i, held)) for i in range(cfg["num_hidden_layers"])},
        "top": jax.jit(lambda words: top_leaves(cfg, words)),
        "forward": {(q, soft): jax.jit(functools.partial(layer_forward, cfg, softmax=soft, held=held, quant=q, drop=drop))
                    for q in (None, "w8a8") for soft in (False, True)},
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
    softmax = attention_layers(cfg)
    for i in range(cfg["num_hidden_layers"]):
        w = prog["layer"][i](words)
        for q in quants:
            hs[q] = [prog["forward"][(q, i in softmax)](w, h) for h in hs[q]]
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
