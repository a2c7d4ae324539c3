"""The family of Solar Open 2 (upstage/Solar-Open2-250B): gated delta-rule
linear-attention layers beside one gated softmax GQA layer without
positions in four (``gqa_layers``), and in every layer an expert layer
with one shared expert, sigmoid scores and renormalised top-k weights, of
which this replica holds one chip's share.  The published keys become the
program's ``GPTConfig`` with a ``KdaConfig`` and a ``MoeConfig``; the seeded
leaves, their stds and the plain reference are ``reference/solar_open2.py``'s;
the counts of work are below.

Needs a program that has linear attention, attention without positions
and with an output gate, and a shared expert (``models/kda.py``,
``GPTConfig.use_rope``/``attn_gate``, ``MoeConfig.shared_size``): on one that
lacks them ``build`` says so and the replica exits at once.
"""

from __future__ import annotations

NEEDS = {"kda", "attention_layers", "use_rope", "attn_gate", "moe"}
NEEDS_MOE = {"shared_size", "score", "norm_topk"}


def _dims(m: dict) -> dict:
    lin = m["linear_attn_config"]
    layers = m["num_hidden_layers"]
    softmax = sum(1 for i in m["gqa_layers"] if i < layers)
    return {
        "h": m["hidden_size"], "H": m["num_attention_heads"], "G": m["num_key_value_heads"], "d": m["head_dim"],
        "H_l": lin["num_heads"], "d_l": lin["head_dim"], "conv": lin["short_conv_kernel_size"], "r": lin["head_dim"],
        "E": m["published"]["n_routed_experts"], "held": m["n_routed_experts"], "k": m["num_experts_per_tok"],
        "f": m["moe_intermediate_size"], "f_s": m["n_shared_experts"] * m["moe_intermediate_size"],
        "layers": layers, "softmax": softmax, "linear": layers - softmax,
    }


def build(model: dict, engine: dict):
    import dataclasses

    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models import moe, transformer

    fields = {f.name for f in dataclasses.fields(transformer.GPTConfig)}
    moe_fields = {f.name for f in dataclasses.fields(moe.MoeConfig)}
    if not (NEEDS <= fields and NEEDS_MOE <= moe_fields):
        raise SystemExit("family solar_open2: this program's GPTConfig has no linear attention, attention "
                         "without positions or shared expert (models/kda.py, use_rope, attn_gate, "
                         "MoeConfig.shared_size): it cannot run the configuration")
    if model["kda_use_full_proj"] or model["first_k_dense_replace"] or model["linear_attn_config"]["num_kv_heads"]:
        raise SystemExit("family solar_open2: full-rank decay projections, leading dense layers and linear "
                         "attention with fewer key-value heads are not what this family builds")
    from chipbench.reference import solar_open2 as ref
    from k8s_device_plugin_tpu.models.kda import KdaConfig

    d = _dims(model)
    cfg = transformer.GPTConfig(
        vocab_size=model["vocab_size"],
        hidden_size=d["h"],
        num_layers=d["layers"],
        num_heads=d["H"],
        num_kv_heads=d["G"],
        head_dim=d["d"],
        intermediate_size=model["intermediate_size"],
        max_seq=engine["page_size"] * engine["max_pages_per_seq"],
        rope_theta=float(model["rope_theta"]),
        rms_norm_eps=model["rms_norm_eps"],
        logits_to_keep=1,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
        use_rope=model["use_rope"],
        attn_gate=model["use_gqa_gate"],
        kda=KdaConfig(n_heads=d["H_l"], head_dim=d["d_l"], conv=d["conv"], rank=d["r"],
                      neg_eigval=model["kda_allow_neg_eigval"]),
        attention_layers=ref.attention_layers(model),
        moe=moe.MoeConfig(
            n_routed=d["E"], n_zero=0, top_k=d["k"], scaling=float(model["routed_scaling_factor"]),
            expert_size=d["f"], held=ref.held_experts(model), shared_size=d["f_s"], score="sigmoid",
            norm_topk=model["norm_topk_prob"],
        ),
    )
    return cfg, transformer.PagedConfig(engine["page_size"], engine["num_pages"], engine["max_pages_per_seq"])


def params_tree(model: dict, seed_words):
    """The served tree in the program's layout (``TransformerLM`` params:
    ``layer_i`` with ``attn`` in a softmax layer, ``kda`` in a linear one,
    ``moe`` in every one), from the reference's own leaf functions: the same
    keys, shapes and stds; q | k | v and their convolutions side by side."""
    import jax.numpy as jnp

    from chipbench.reference import solar_open2 as ref

    d, held = ref.dims(model), ref.held_experts(model)
    h = d["h"]
    top = ref.top_leaves(model, seed_words)
    tree = {
        "embed": {"embedding": top["embed"]},
        "final_norm": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["lm_head"]},
    }
    softmax = ref.attention_layers(model)
    for i in range(model["num_hidden_layers"]):
        w = ref.layer_leaves(model, seed_words, i, held)
        block = {
            "attn_norm": {"scale": w["norm_a"]},
            "mlp_norm": {"scale": w["norm_b"]},
            "moe": {
                "router": w["moe/router"], "select_bias": w["moe/bias"],
                **{name: w[f"moe/{name}"] for name in (
                    "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down")},
            },
        }
        if i in softmax:
            heads = {"query": d["H"], "key": d["G"], "value": d["G"], "gate": d["H"]}
            leaf = {"query": "q", "key": "k", "value": "v", "gate": "gate"}
            block["attn"] = {
                **{n: {"kernel": w[f"attn/{leaf[n]}"].reshape(h, heads[n], d["d"])} for n in heads},
                "out": {"kernel": w["attn/o"].reshape(d["H"], d["d"], h)},
            }
        else:
            block["kda"] = {
                "in_proj": {"kernel": jnp.concatenate([w[f"kda/{n}"] for n in ("q", "k", "v")], axis=1)},
                "conv_kernel": jnp.concatenate([w[f"kda/conv_{n}"] for n in ("q", "k", "v")], axis=1),
                **{n: {"kernel": w[f"kda/{n}"]} for n in ("f_a", "f_b", "g_a", "g_b")},
                "b_proj": {"kernel": w["kda/b"]},
                "out_proj": {"kernel": w["kda/o"]},
                **{n: w[f"kda/{n}"] for n in ("A_log", "dt_bias", "o_norm")},
            }
        tree[f"layer_{i}"] = block
    return tree


def served_gaps(conf: dict, seed: int, cases: list[dict], pad_to: int, control: bool) -> list[dict]:
    from chipbench.reference import solar_open2 as ref

    return ref.served_gaps(conf, seed, cases, pad_to, control=control)


# ------------------------------------------------------- counts of work ----
# From the shapes and the run's counters; called in the parent, which never
# imports JAX.


def attention_params(m: dict) -> int:
    """A softmax layer's matrices: W_q, W_k, W_v, the gate, W_o."""
    d = _dims(m)
    return d["h"] * d["H"] * d["d"] * 3 + 2 * d["h"] * d["G"] * d["d"]


def linear_params(m: dict) -> int:
    """A linear-attention layer's matrices: W_q, W_k, W_v, W_o, the
    low-rank decay and gate projections, W_b."""
    d = _dims(m)
    w = d["H_l"] * d["d_l"]
    return 4 * d["h"] * w + 2 * (d["h"] * d["r"] + d["r"] * w) + d["h"] * d["H_l"]


def moe_dense_params(m: dict) -> int:
    """An expert layer outside its routed experts: the router, the shared expert."""
    d = _dims(m)
    return d["h"] * d["E"] + 3 * d["h"] * d["f_s"]


def expert_params(m: dict) -> int:
    d = _dims(m)
    return 3 * d["h"] * d["f"]


def vector_params(m: dict) -> int:
    """Every layer's two norms and selection bias; a linear layer's three
    convolutions, decay vectors and output norm."""
    d = _dims(m)
    w = d["H_l"] * d["d_l"]
    return d["layers"] * (2 * d["h"] + d["E"]) + d["linear"] * (3 * w * d["conv"] + d["H_l"] + w + d["d_l"])


def dense_weight_bytes(m: dict) -> int:
    """Bytes a decode step reads whatever the routing: every matrix and
    vector outside the routed experts, and the head, once, bfloat16 (the
    embedding is a row lookup)."""
    d = _dims(m)
    matrices = d["softmax"] * attention_params(m) + d["linear"] * linear_params(m) + d["layers"] * moe_dense_params(m)
    return 2 * (matrices + vector_params(m) + d["h"] * m["vocab_size"] + d["h"])


def touched_experts(m: dict, contexts: list[int], ctx: dict) -> float:
    """Held experts a decode step reads in one expert layer: what the run's
    counters say a step touched on average
    (``tpu_engine_moe_decode_experts_touched_total`` over
    ``tpu_engine_moe_decode_layer_steps_total``, the window's difference),
    NOT all that are held.  Without the counters, uniform routing's
    expectation for this many tokens."""
    d = _dims(m)
    scraped = ctx.get("scraped") or {}
    after, before = scraped.get("after") or {}, scraped.get("before") or {}
    steps = after.get("tpu_engine_moe_decode_layer_steps_total", 0) - before.get("tpu_engine_moe_decode_layer_steps_total", 0)
    if steps > 0:
        name = "tpu_engine_moe_decode_experts_touched_total"
        return (after.get(name, 0) - before.get(name, 0)) / steps
    return d["held"] * (1.0 - (1.0 - d["k"] / d["E"]) ** len(contexts))


def decode_expert_bytes(m: dict, contexts: list[int], ctx: dict) -> float:
    """The touched experts' weights, every expert layer, once a step."""
    return 2.0 * _dims(m)["layers"] * touched_experts(m, contexts, ctx) * expert_params(m)


def state_bytes_per_slot(m: dict, ctx: dict) -> float:
    """Bytes of one slot's state over the linear-attention layers: the
    program's gauge ``tpu_engine_slot_state_bytes`` over the slots where the
    run scraped it, else from the shapes (float32 state, bfloat16
    convolution tail)."""
    gauge = ((ctx.get("scraped") or {}).get("after") or {}).get("tpu_engine_slot_state_bytes")
    if gauge and ctx.get("slots"):
        return gauge / ctx["slots"]
    d = _dims(m)
    w = d["H_l"] * d["d_l"]
    return d["linear"] * (4 * d["H_l"] * d["d_l"] ** 2 + 2 * (d["conv"] - 1) * 3 * w)


def decode_state_bytes(m: dict, contexts: list[int], ctx: dict) -> float:
    """The state of every live slot read AND written once a step."""
    return 2.0 * state_bytes_per_slot(m, ctx) * len(contexts)


def cache_bytes_per_token(m: dict, ctx: dict) -> float:
    """A cached position's bytes over the softmax layers: the program's
    gauge ``tpu_engine_cache_bytes_per_token`` where the run scraped it,
    else from the shapes (K and V of the key-value heads, bfloat16)."""
    gauge = ((ctx.get("scraped") or {}).get("after") or {}).get("tpu_engine_cache_bytes_per_token")
    d = _dims(m)
    return gauge or 2.0 * 2 * d["softmax"] * d["G"] * d["d"]


def linear_layers(m: dict) -> int:
    """Linear-attention layers: one ``kda_decode`` call each a decode step."""
    return _dims(m)["linear"]


def kda_decode_bytes(m: dict, slots: float) -> float:
    """The least bytes ONE call of the recurrence kernel moves (one layer,
    one decode step) over ``slots`` active slots: each slot's state read
    and written once, float32, and its q, k, v, g, beta in and o out."""
    d = _dims(m)
    vectors = 5 * d["H_l"] * d["d_l"] + d["H_l"]
    return slots * 4.0 * (2 * d["H_l"] * d["d_l"] ** 2 + vectors)


def token_flops(m: dict, pos: int, with_head: bool) -> float:
    """Forward FLOPs of one token at position ``pos``: two per parameter of
    the matrices it meets (held experts at uniform routing's expectation of
    top_k x held / E assignments a layer, the shared expert always), the
    recurrence's 4 H_l d_l^2 a linear layer (the decayed recall and the
    rank-1 write, the read-out), 4 H d per attended position a softmax
    layer, the head only where a logit is needed."""
    d = _dims(m)
    matrices = d["softmax"] * attention_params(m) + d["linear"] * linear_params(m)
    matrices += d["layers"] * (moe_dense_params(m) + d["k"] * d["held"] / d["E"] * expert_params(m))
    flops = 2.0 * matrices + 4.0 * d["linear"] * d["H_l"] * d["d_l"] ** 2
    flops += 4.0 * d["softmax"] * d["H"] * d["d"] * (pos + 1)
    if with_head:
        flops += 2.0 * d["h"] * m["vocab_size"]
    return flops


def request_flops(m: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Prefill of the prompt (the head at its last position only) and the
    decode steps that produce output tokens 2..n."""
    total = sum(token_flops(m, pos, pos == prompt_tokens - 1) for pos in range(prompt_tokens))
    return total + sum(token_flops(m, prompt_tokens + i - 1, True) for i in range(1, output_tokens))


def decode_step(m: dict, contexts: list[int], ctx: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots whose contexts hold
    ``contexts`` positions each: every weight outside the routed experts and
    the head once, the touched experts' weights once, each live slot's
    state read and written once, each context's keys and values once and
    one row written."""
    flops = sum(token_flops(m, c, True) for c in contexts)
    kv = cache_bytes_per_token(m, ctx) * sum(c + 1 for c in contexts)
    nbytes = dense_weight_bytes(m) + decode_expert_bytes(m, contexts, ctx) + decode_state_bytes(m, contexts, ctx) + kv
    return flops, float(nbytes)
