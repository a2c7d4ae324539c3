"""The family of LongCat-Flash (meituan-longcat/LongCat-Flash-Chat):
shortcut-connected double-layers, each two latent attentions, two dense
SwiGLUs and one expert layer with zero-computation experts, of which this
replica holds one chip's share.  The published keys become the program's
``GPTConfig`` with an ``MlaConfig`` and a ``MoeConfig`` (two blocks and two
cache-tree layers a double-layer); the seeded leaves, their stds and the
plain reference are ``reference/longcat_flash.py``'s; the counts of work are
below.

Needs a program that has latent attention and the serving expert layer
(``models/mla.py``, ``models/moe.py``): on one that lacks them ``build``
says so and the replica exits at once.
"""

from __future__ import annotations


def _dims(m: dict) -> dict:
    heads = m["num_attention_heads"]
    return {
        "h": m["hidden_size"], "H": heads, "r_q": m["q_lora_rank"], "r_kv": m["kv_lora_rank"],
        "qk": m["qk_nope_head_dim"] + m["qk_rope_head_dim"], "d_n": m["qk_nope_head_dim"],
        "d_r": m["qk_rope_head_dim"], "d_v": m["v_head_dim"], "ff": m["ffn_hidden_size"],
        "f": m["expert_ffn_hidden_size"], "width": m["published"]["n_routed_experts"] + m["zero_expert_num"],
        "held": m["n_routed_experts"], "k": m["moe_topk"], "layers": m["num_layers"],
        "row": m["kv_lora_rank"] + m["qk_rope_head_dim"],
    }


def build(model: dict, engine: dict):
    import dataclasses

    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models import transformer

    fields = {f.name for f in dataclasses.fields(transformer.GPTConfig)}
    if not {"mla", "moe"} <= fields:
        raise SystemExit("family longcat_flash: this program's GPTConfig has no latent attention or expert layer "
                         "(models/mla.py, models/moe.py): it cannot run the configuration")
    from chipbench.reference import longcat_flash as ref
    from k8s_device_plugin_tpu.models.mla import MlaConfig, mla_scale
    from k8s_device_plugin_tpu.models.moe import MoeConfig

    h = model["hidden_size"]
    cfg = transformer.GPTConfig(
        vocab_size=model["vocab_size"],
        hidden_size=h,
        num_layers=2 * model["num_layers"],  # two blocks, one attention each, a double-layer
        num_heads=model["num_attention_heads"],
        intermediate_size=model["ffn_hidden_size"],
        max_seq=engine["page_size"] * engine["max_pages_per_seq"],
        rope_theta=float(model["rope_theta"]),
        rms_norm_eps=model["rms_norm_eps"],
        logits_to_keep=1,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
        mla=MlaConfig(
            q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
            rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
            scale_q=mla_scale(model["mla_scale_q_lora"], h, model["q_lora_rank"]),
            scale_kv=mla_scale(model["mla_scale_kv_lora"], h, model["kv_lora_rank"]),
        ),
        moe=MoeConfig(
            n_routed=model["published"]["n_routed_experts"], n_zero=model["zero_expert_num"],
            top_k=model["moe_topk"], scaling=float(model["routed_scaling_factor"]),
            expert_size=model["expert_ffn_hidden_size"], held=ref.held_experts(model),
        ),
    )
    return cfg, transformer.PagedConfig(engine["page_size"], engine["num_pages"], engine["max_pages_per_seq"])


def params_tree(model: dict, seed_words):
    """The served tree in the program's layout (``TransformerLM`` params:
    ``layer_{2i}`` and ``layer_{2i+1}`` are double-layer i's two blocks, the
    first with a ``moe`` subtree), from the reference's own leaf functions:
    the same keys, shapes and stds."""
    from chipbench.reference import longcat_flash as ref

    d, held = ref.dims(model), ref.held_experts(model)
    h, heads = d["h"], d["H"]
    top = ref.top_leaves(model, seed_words)
    tree = {
        "embed": {"embedding": top["embed"]},
        "final_norm": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["lm_head"]},
    }
    for i in range(model["num_layers"]):
        w = ref.layer_leaves(model, seed_words, i, held)
        for j in (0, 1):
            a = {name: w[f"attn{j}/{name}"] for name in ref.ATTN}
            block = {
                "attn": {
                    "q_a": {"kernel": a["q_a"]}, "q_norm": {"scale": a["q_norm"]},
                    "q_b": {"kernel": a["q_b"].reshape(d["r_q"], heads, d["d_n"] + d["d_r"])},
                    "kv_a": {"kernel": a["kv_a"]}, "kv_norm": {"scale": a["kv_norm"]},
                    "kv_b": a["kv_b"].reshape(d["r_kv"], heads, d["d_n"] + d["d_v"]),
                    "out": {"kernel": a["o"].reshape(heads, d["d_v"], h)},
                },
                "mlp": {n: {"kernel": w[f"mlp{j}/{n}"]} for n in ("gate", "up", "down")},
                "attn_norm": {"scale": w[f"norm{j}a"]},
                "mlp_norm": {"scale": w[f"norm{j}b"]},
            }
            if j == 0:
                block["moe"] = {
                    "router": w["moe/router"], "select_bias": w["moe/bias"],
                    **{f"experts_{n}": w[f"moe/experts_{n}"] for n in ("gate", "up", "down")},
                }
            tree[f"layer_{2 * i + j}"] = block
    return tree


def served_gaps(conf: dict, seed: int, cases: list[dict], pad_to: int, control: bool) -> list[dict]:
    from chipbench.reference import longcat_flash as ref

    return ref.served_gaps(conf, seed, cases, pad_to, control=control)


# ------------------------------------------------------- counts of work ----
# From the shapes and the run's counters; called in the parent, which never
# imports JAX.


def attention_params(m: dict) -> int:
    """One latent attention's matrices: q_a, q_b, kv_a, kv_b, o."""
    d = _dims(m)
    return (d["h"] * d["r_q"] + d["r_q"] * d["H"] * d["qk"] + d["h"] * d["row"]
            + d["r_kv"] * d["H"] * (d["d_n"] + d["d_v"]) + d["H"] * d["d_v"] * d["h"])


def layer_dense_params(m: dict) -> int:
    """A double-layer outside its experts: two attentions, two SwiGLUs, the router."""
    d = _dims(m)
    return 2 * attention_params(m) + 2 * 3 * d["h"] * d["ff"] + d["h"] * d["width"]


def layer_vector_params(m: dict) -> int:
    """Four block norms, the two latent norms of each attention, the selection bias."""
    d = _dims(m)
    return 4 * d["h"] + 2 * (d["r_q"] + d["r_kv"]) + d["width"]


def expert_params(m: dict) -> int:
    d = _dims(m)
    return 3 * d["h"] * d["f"]


def dense_weight_bytes(m: dict) -> int:
    """Bytes a decode step reads whatever the routing: every matrix and
    vector outside the experts and the head once, bfloat16 (the embedding
    is a row lookup)."""
    d = _dims(m)
    return 2 * (d["layers"] * (layer_dense_params(m) + layer_vector_params(m)) + d["h"] * m["vocab_size"] + d["h"])


def touched_experts(m: dict, contexts: list[int], ctx: dict) -> float:
    """Held experts a decode step reads in one expert layer: what the run's
    counters say a step touched on average
    (``tpu_engine_moe_decode_experts_touched_total`` over
    ``tpu_engine_moe_decode_layer_steps_total``, the window's difference),
    NOT all that are held: a program that skips an untouched expert must
    not read over 100 %.  Without the counters, uniform routing's
    expectation for this many tokens."""
    d = _dims(m)
    scraped = ctx.get("scraped") or {}
    after, before = scraped.get("after") or {}, scraped.get("before") or {}
    steps = after.get("tpu_engine_moe_decode_layer_steps_total", 0) - before.get("tpu_engine_moe_decode_layer_steps_total", 0)
    if steps > 0:
        name = "tpu_engine_moe_decode_experts_touched_total"
        return (after.get(name, 0) - before.get(name, 0)) / steps
    return d["held"] * (1.0 - (1.0 - d["k"] / d["width"]) ** len(contexts))


def decode_expert_bytes(m: dict, contexts: list[int], ctx: dict) -> float:
    """The touched experts' weights, every expert layer, once a step."""
    return 2.0 * _dims(m)["layers"] * touched_experts(m, contexts, ctx) * expert_params(m)


def cache_bytes_per_token(m: dict, ctx: dict) -> float:
    """A cached position's bytes over all attentions: the program's gauge
    ``tpu_engine_cache_bytes_per_token`` where the run scraped it, else
    from the shapes (one bfloat16 latent row an attention)."""
    gauge = ((ctx.get("scraped") or {}).get("after") or {}).get("tpu_engine_cache_bytes_per_token")
    d = _dims(m)
    return gauge or 2.0 * 2 * d["layers"] * d["row"]


def decode_latent_bytes(m: dict, contexts: list[int], ctx: dict) -> float:
    """Each live context's latent rows read once and one row written."""
    return cache_bytes_per_token(m, ctx) * sum(c + 1 for c in contexts)


def token_flops(m: dict, pos: int, with_head: bool) -> float:
    """Forward FLOPs of one token at position ``pos``: two per parameter of
    the matrices it meets (held experts at uniform routing's expectation of
    top_k x held / (E + Z) assignments a layer, 0.25 here: 0.4 % of the
    token's FLOPs), attention in the EXPANDED form's count, 2 H (d_n + d_r)
    for the scores and 2 H d_v for the values of each attended position and
    attention (the lesser of the two forms: the absorbed form the program
    decodes with costs 2 H (2 r_kv + d_r), so no share of a peak is
    flattered), the head only where a logit is needed."""
    d = _dims(m)
    per_layer = layer_dense_params(m) + d["k"] * d["held"] / d["width"] * expert_params(m)
    flops = 2.0 * d["layers"] * per_layer
    flops += 2.0 * d["layers"] * 2.0 * d["H"] * (d["qk"] + d["d_v"]) * (pos + 1)
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
    ``contexts`` positions each: every weight outside the experts and the
    head once, the touched experts' weights once, each context's latent
    rows once and one row written."""
    flops = sum(token_flops(m, c, True) for c in contexts)
    nbytes = dense_weight_bytes(m) + decode_expert_bytes(m, contexts, ctx) + decode_latent_bytes(m, contexts, ctx)
    return flops, float(nbytes)
