"""Plain reference of the Falcon-H1 decoder (tiiuae/Falcon-H1-34B-Instruct's
block: ONE pre-norm feeding grouped-query attention and a Mamba-2 mixer side
by side, their scaled outputs summed into the residual, then a scaled
SwiGLU), teacher-forced over prompt + served tokens.  Float32 at
``precision="highest"``; the recurrence as a ``lax.scan`` over time, one
token at a time (no chunks, no cache, no batching); one layer at a time
with the layer's leaves made again from the seed; the head in blocks of the
vocabulary (whole, in float32, it is 5.3 GB).  Shares no code with the
program (``k8s_device_plugin_tpu/``).

The equations, from the published ``config.json`` keys (where this text and
the keys disagree, the keys decide).  H heads of P, G groups, state N,
kernel K, ``conv_dim`` = d_ssm + 2 G N::

    x = embed[ids] * embedding_multiplier
    each layer:
      u = RMSNorm(x; input_layernorm, rms_norm_eps)
      a = Wo softmax_causal(rope(Wq u') rope(Wk u' * key_multiplier)^T / sqrt(head_dim)) (Wv u')
          with u' = u * attention_in_multiplier; no window, no bias
      zxbcdt = (W_in (u * ssm_in_multiplier)) * mup     mup: the five ssm_multipliers over z | x | B | C | dt
      z, xBC, dt = split(zxbcdt, [d_ssm, conv_dim, H])
      xBC = silu(causal depthwise conv1d(xBC; w [K, conv_dim], bias))    K-1 zeros before the first position
      xs, B, C = split(xBC, [d_ssm, G N, G N]);  head h reads group h // (H/G)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)
      h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (outer) B_t
      y_t = h_t . C_t + D xs_t
      y = RMSNorm over each group's d_ssm/G channels of (y * silu(z)), times a weight [d_ssm]
      x = x + a * attention_out_multiplier + (W_out y) * ssm_out_multiplier
      v = RMSNorm(x; pre_ff_layernorm)
      x = x + W_down(silu(W_gate v * mlp_multipliers[0]) * W_up v) * mlp_multipliers[1]
    logits = W_head RMSNorm(x; final) * lm_head_multiplier

Read so, and listed under ``assumed`` in the configuration's file:
``mamba_expand`` is unused (``mamba_d_ssm`` is given); ``attn_layer_indices``
null means attention in every layer; ``mamba_use_mlp`` true means the block
has its feed-forward; ``mamba_rms_norm`` true with ``mamba_norm_before_gate``
false is the gate-then-norm above; ``mamba_chunk_size`` is the scan's chunk,
an algorithm's choice (scan and recurrence give the same numbers).

Departure from the published description, because the program does the same
and the weights are random: rotary pairs are (2i, 2i+1) and not (i, i + d/2)
(a fixed permutation of the query/key columns), as ``reference/llm.py``.

**Seeded leaves.**  With std 1/sqrt(fan_in) everywhere the published
multipliers (``lm_head_multiplier`` 0.0078, ``attention_out_multiplier``
0.0375, ``ssm_out_multiplier`` 0.088, ``mlp_multipliers[1]`` 0.011) would make
every branch vanish beside an embedding scaled by 5.66, and all logits would
lie within a hundredth of each other: any arithmetic would pass.  The
multipliers are the model's and stay; ``leaf_stds`` divides each leaf's std by
the multiplier that follows it, so that queries, keys, values, gates and the
mixer's segments come out at the order of 1, attention, mixer and
feed-forward each add about 0.7 of the embedded stream's size to the
residual, B and C are large enough that the recurrent part of ``y``
outweighs the skip ``D xs``, and the logits spread by a few units.
``A_log = log(U[1, 16])``, ``dt_bias`` = inverse softplus of a log-uniform
step in [0.001, 0.1], ``D`` = 1, as Mamba-2 initialises them.

``quant="w8a8"`` is the control: every matmul's weights rounded to int8 per
output channel and its input to int8 per token (the recurrence stays
float32).  ``drop_mixer`` sets the mixer's output to 0: the proof that the
comparison sees the mixer.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

VOCAB_BLOCKS = 16  # the embedding's rows and the head's columns are made block by block


# ------------------------------------------------------------------ leaves


def dims(cfg: dict) -> dict:
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {
        "h": cfg["hidden_size"], "nh": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "ff": cfg["intermediate_size"], "H": heads, "P": p,
        "G": cfg["mamba_n_groups"], "N": cfg["mamba_d_state"], "K": cfg["mamba_d_conv"],
        "d_ssm": cfg["mamba_d_ssm"], "gn": gn, "conv_dim": cfg["mamba_d_ssm"] + 2 * gn,
    }


def leaf_stds(cfg: dict) -> dict[str, float]:
    """The seeded std of every matrix leaf (module docstring, Seeded
    leaves): 1/sqrt(fan_in) times a gain, over the multiplier that scales
    the leaf's output."""
    d = dims(cfg)
    h, ssm_in = d["h"], cfg["ssm_in_multiplier"]
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    att_in = cfg["attention_in_multiplier"]
    return {
        "embed": 1.0 / cfg["embedding_multiplier"],
        "attn/query": 1.0 / (att_in * math.sqrt(h)),
        "attn/key": 1.0 / (att_in * cfg["key_multiplier"] * math.sqrt(h)),
        "attn/value": 1.0 / (att_in * math.sqrt(h)),
        # a softmax average of values of size 1 has a size near 0.35
        "attn/out": 0.7 / (0.35 * cfg["attention_out_multiplier"] * math.sqrt(d["nh"] * d["hd"])),
        "mixer/in_z": 1.0 / (ssm_in * mz * math.sqrt(h)),
        "mixer/in_x": 1.0 / (ssm_in * mx * math.sqrt(h)),
        "mixer/in_B": 3.0 / (ssm_in * mb * math.sqrt(h)),
        "mixer/in_C": 3.0 / (ssm_in * mc * math.sqrt(h)),
        "mixer/in_dt": 1.0 / (ssm_in * mdt * math.sqrt(h)),
        "mixer/conv_kernel": 0.5,
        "mixer/conv_bias": 0.1,
        "mixer/out_proj": 0.7 / (cfg["ssm_out_multiplier"] * math.sqrt(d["d_ssm"])),
        "mlp/gate": 1.0 / (cfg["mlp_multipliers"][0] * math.sqrt(h)),
        "mlp/up": 1.0 / math.sqrt(h),
        # silu(gate) * up of sizes 1 has a size near 0.5
        "mlp/down": 0.7 / (0.5 * cfg["mlp_multipliers"][1] * math.sqrt(d["ff"])),
        "lm_head": 2.0 / (cfg["lm_head_multiplier"] * math.sqrt(h)),
    }


def _normal(key, shape, std, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def layer_leaves(cfg: dict, seed, layer) -> dict[str, jax.Array]:
    """One layer's leaves under the reference's own names, each from a key
    of its own (seed, layer, name).  Matrices and norm scales bfloat16;
    ``A_log``, ``dt_bias`` and ``D`` float32."""
    d, std = dims(cfg), leaf_stds(cfg)
    key = lambda name: weights.leaf_key(seed, layer, name)  # noqa: E731
    h = d["h"]
    shapes = {
        "attn/query": (h, d["nh"] * d["hd"]), "attn/key": (h, d["kv"] * d["hd"]),
        "attn/value": (h, d["kv"] * d["hd"]), "attn/out": (d["nh"] * d["hd"], h),
        "mixer/in_z": (h, d["d_ssm"]), "mixer/in_x": (h, d["d_ssm"]), "mixer/in_B": (h, d["gn"]),
        "mixer/in_C": (h, d["gn"]), "mixer/in_dt": (h, d["H"]),
        "mixer/conv_kernel": (d["K"], d["conv_dim"]), "mixer/conv_bias": (d["conv_dim"],),
        "mixer/out_proj": (d["d_ssm"], h),
        "mlp/gate": (h, d["ff"]), "mlp/up": (h, d["ff"]), "mlp/down": (d["ff"], h),
    }
    out = {name: _normal(key(name), shape, std[name]) for name, shape in shapes.items()}
    for name, width in (("attn_norm", h), ("mlp_norm", h), ("mixer/norm", d["d_ssm"])):
        out[name] = _normal(key(name), (width,), 0.1, 1.0)
    out["mixer/A_log"] = jnp.log(jax.random.uniform(key("mixer/A_log"), (d["H"],), jnp.float32, 1.0, 16.0))
    step = jnp.exp(jax.random.uniform(key("mixer/dt_bias"), (d["H"],), jnp.float32, math.log(1e-3), math.log(1e-1)))
    out["mixer/dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    out["mixer/D"] = jnp.ones((d["H"],), jnp.float32)
    return out


def final_norm(cfg: dict, seed) -> jax.Array:
    return _normal(weights.leaf_key(seed, -1, "final_norm"), (cfg["hidden_size"],), 0.1, 1.0)


def vocab_block(cfg: dict) -> int:
    if cfg["vocab_size"] % VOCAB_BLOCKS:
        raise ValueError(f"vocab_size {cfg['vocab_size']} is not a multiple of {VOCAB_BLOCKS}")
    return cfg["vocab_size"] // VOCAB_BLOCKS


def embed_block(cfg: dict, seed, block) -> jax.Array:
    """Rows [block * B, (block + 1) * B) of the embedding, [B, hidden]."""
    key = jax.random.fold_in(weights.leaf_key(seed, -1, "embed"), block)
    return _normal(key, (vocab_block(cfg), cfg["hidden_size"]), leaf_stds(cfg)["embed"])


def head_block(cfg: dict, seed, block) -> jax.Array:
    """Columns [block * B, (block + 1) * B) of the head, [hidden, B]."""
    key = jax.random.fold_in(weights.leaf_key(seed, -1, "lm_head"), block)
    return _normal(key, (cfg["hidden_size"], vocab_block(cfg)), leaf_stds(cfg)["lm_head"])


# ----------------------------------------------------------------- forward


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _matmul(x, w, quant):
    """x [..., in] @ w [in, out] in float32."""
    if quant == "w8a8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [seq, heads, head_dim]; rotate pairs (2i, 2i+1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def attention(cfg: dict, w: dict, u, quant):
    d = dims(cfg)
    seq, nh, kv, hd = u.shape[0], d["nh"], d["kv"], d["hd"]
    pos = jnp.arange(seq)
    u = u * cfg["attention_in_multiplier"]
    q = _matmul(u, w["attn/query"], quant).reshape(seq, nh, hd)
    k = (_matmul(u, w["attn/key"], quant) * cfg["key_multiplier"]).reshape(seq, kv, hd)
    v = _matmul(u, w["attn/value"], quant).reshape(seq, kv, hd)
    theta = float(cfg["rope_theta"])  # 1e11 in the file: a whole number past 32 bits
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k, v = jnp.repeat(k, nh // kv, axis=1), jnp.repeat(v, nh // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * hd ** -0.5
    p = jax.nn.softmax(jnp.where((pos[None, :] <= pos[:, None])[None], s, -1e30), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision="highest").reshape(seq, nh * hd)
    return _matmul(a, w["attn/out"], quant)


def mixer(cfg: dict, w: dict, u, quant):
    d = dims(cfg)
    seq, heads, p, groups, n, k = u.shape[0], d["H"], d["P"], d["G"], d["N"], d["K"]
    u = u * cfg["ssm_in_multiplier"]
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    z = _matmul(u, w["mixer/in_z"], quant) * mz
    xbc = jnp.concatenate([
        _matmul(u, w["mixer/in_x"], quant) * mx,
        _matmul(u, w["mixer/in_B"], quant) * mb,
        _matmul(u, w["mixer/in_C"], quant) * mc,
    ], axis=-1)
    dt = _matmul(u, w["mixer/in_dt"], quant) * mdt
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(padded[i : i + seq] * w["mixer/conv_kernel"][i] for i in range(k)) + w["mixer/conv_bias"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, : d["d_ssm"]].reshape(seq, heads, p)
    b = xbc[:, d["d_ssm"] : d["d_ssm"] + d["gn"]].reshape(seq, groups, n)
    c = xbc[:, d["d_ssm"] + d["gn"] :].reshape(seq, groups, n)
    b, c = jnp.repeat(b, heads // groups, axis=1), jnp.repeat(c, heads // groups, axis=1)  # [seq, H, N]
    dt = jax.nn.softplus(dt + w["mixer/dt_bias"])  # [seq, H]
    a_neg = -jnp.exp(w["mixer/A_log"])

    def token(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * a_neg)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t, precision="highest")

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32), (xs, b, c, dt))
    y = (y + w["mixer/D"][:, None] * xs).reshape(seq, d["d_ssm"]) * jax.nn.silu(z)
    yg = y.reshape(seq, groups, d["d_ssm"] // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return _matmul(yg.reshape(seq, d["d_ssm"]) * w["mixer/norm"], w["mixer/out_proj"], quant)


def layer_forward(cfg: dict, w: dict, h, quant=None, drop_mixer=False):
    """One block on one sequence h [seq, hidden] (float32)."""
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    eps = cfg["rms_norm_eps"]
    u = _rmsnorm(h, w["attn_norm"], eps)
    h = h + attention(cfg, w, u, quant) * cfg["attention_out_multiplier"]
    if not drop_mixer:
        h = h + mixer(cfg, w, u, quant) * cfg["ssm_out_multiplier"]
    v = _rmsnorm(h, w["mlp_norm"], eps)
    gate = _matmul(v, w["mlp/gate"], quant) * cfg["mlp_multipliers"][0]
    ff = _matmul(jax.nn.silu(gate) * _matmul(v, w["mlp/up"], quant), w["mlp/down"], quant)
    return h + ff * cfg["mlp_multipliers"][1]


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, drop_mixer: bool):
    """The jitted pieces, once per configuration; the seed is their argument."""
    cfg = json.loads(cfg_json)
    return {
        "layer": jax.jit(lambda words, i: layer_leaves(cfg, words, i)),
        "final_norm": jax.jit(lambda words: final_norm(cfg, words)),
        "embed_block": jax.jit(lambda words, blk: embed_block(cfg, words, blk)),
        "head_block": jax.jit(lambda words, blk: head_block(cfg, words, blk)),
        "forward": {q: jax.jit(functools.partial(layer_forward, cfg, quant=q, drop_mixer=drop_mixer))
                    for q in (None, "w8a8")},
    }


def embed_rows(cfg: dict, seed: int, ids: np.ndarray) -> np.ndarray:
    """The embedding's rows of ``ids`` (any shape), float32, block by block."""
    prog = _programs(json.dumps(cfg, sort_keys=True), False)
    words, size = weights.seed_words(seed), vocab_block(cfg)
    out = np.zeros(ids.shape + (cfg["hidden_size"],), np.float32)
    for blk in sorted(set((ids // size).ravel().tolist())):
        rows = np.asarray(prog["embed_block"](words, jnp.int32(blk)).astype(jnp.float32))
        hit = ids // size == blk
        out[hit] = rows[ids[hit] - blk * size]
    return out


def forward_hidden(cfg: dict, seed: int, ids: np.ndarray, quants=(None,), drop_mixer=False):
    """For each ``quant``: the hidden states after the final norm, a list of
    [seq, hidden] per sequence.  ``ids`` [n_seq, seq] int32, every sequence
    padded to the same length (causal, attention and recurrence alike:
    padding past a sequence's end changes nothing before it)."""
    prog = _programs(json.dumps(cfg, sort_keys=True), bool(drop_mixer))
    words = weights.seed_words(seed)
    x = embed_rows(cfg, seed, ids) * np.float32(cfg["embedding_multiplier"])
    hs = {q: [jnp.asarray(row) for row in x] for q in quants}
    for i in range(cfg["num_hidden_layers"]):
        w = prog["layer"](words, jnp.int32(i))
        for q in quants:
            hs[q] = [prog["forward"][q](w, h) for h in hs[q]]
        del w
    final = prog["final_norm"](words).astype(jnp.float32)
    return {q: [_rmsnorm(h, final, cfg["rms_norm_eps"]) for h in hs[q]] for q in quants}


@functools.partial(jax.jit, static_argnames=("quant",))
def _block_logits(h_rows, block, quant=None):
    return _matmul(h_rows, block.astype(jnp.float32), quant)


def logit_rows(cfg: dict, seed: int, h_rows, quant=None) -> np.ndarray:
    """Logits of some rows, [rows, vocab] float32 on the host: the head one
    block of the vocabulary at a time."""
    prog = _programs(json.dumps(cfg, sort_keys=True), False)
    words = weights.seed_words(seed)
    # (The control's scales are per token over the hidden axis and per
    # output channel: neither depends on the block.)
    out = [
        np.asarray(_block_logits(h_rows, prog["head_block"](words, jnp.int32(blk)), quant=quant))
        for blk in range(VOCAB_BLOCKS)
    ]
    return np.concatenate(out, axis=-1) * np.float32(cfg["lm_head_multiplier"])


def served_gaps(cfg: dict, seed: int, cases: list[dict], pad_to: int, control: bool = False,
                drop_mixer: bool = False) -> list[dict]:
    """For each case ``{"prompt": [...], "tokens": [...]}``: at every served
    position the gap by which the served token's reference logit lies below
    the reference's best; with ``control`` also the gap of the token the
    w8a8 control puts first there."""
    ids = np.zeros((len(cases), pad_to), np.int32)
    for r, c in enumerate(cases):
        seq = list(c["prompt"]) + list(c["tokens"])
        ids[r, : len(seq)] = seq
    quants = (None, "w8a8") if control else (None,)
    hidden = forward_hidden(cfg, seed, ids, quants, drop_mixer)
    out = []
    for r, c in enumerate(cases):
        lo, n = len(c["prompt"]) - 1, len(c["tokens"])
        ref = logit_rows(cfg, seed, hidden[None][r][lo : lo + n])
        served = np.asarray(c["tokens"])
        gap = ref.max(axis=-1) - ref[np.arange(n), served]
        row = {"gaps": gap.tolist(), "ref_argmax": ref.argmax(axis=-1).tolist()}
        if control:
            pick = logit_rows(cfg, seed, hidden["w8a8"][r][lo : lo + n], quant="w8a8").argmax(axis=-1)
            row["control_gaps"] = (ref.max(axis=-1) - ref[np.arange(n), pick]).tolist()
        out.append(row)
    return out
