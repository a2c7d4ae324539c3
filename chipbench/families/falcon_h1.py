"""The family of Falcon-H1 (``model_type`` ``falcon_h1``): a Mamba-2 mixer
beside grouped-query attention in every block, muP multipliers, a head
width that is not ``hidden_size / num_attention_heads``.  The published keys
become the program's ``GPTConfig`` with a ``MambaConfig`` and
``Multipliers``; the seeded leaves, their stds and the plain reference are
``reference/falcon_h1.py``'s; the counts of work are below.

Needs a program that has the mixer (``models/ssm.py``): on one that lacks
it ``build`` says so and the replica exits at once.
"""

from __future__ import annotations


def _dims(m: dict) -> dict:
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    return {
        "h": m["hidden_size"], "q": m["num_attention_heads"] * m["head_dim"],
        "kv": m["num_key_value_heads"] * m["head_dim"], "ff": m["intermediate_size"],
        "d_ssm": m["mamba_d_ssm"], "conv_dim": m["mamba_d_ssm"] + 2 * gn,
        "heads": m["mamba_n_heads"], "in": 2 * m["mamba_d_ssm"] + 2 * gn + m["mamba_n_heads"],
        "state": m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"],
    }


def build(model: dict, engine: dict):
    import dataclasses

    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models import transformer

    fields = {f.name for f in dataclasses.fields(transformer.GPTConfig)}
    if not {"mixer", "multipliers", "head_dim", "logits_to_keep"} <= fields:
        raise SystemExit("family falcon_h1: this program's GPTConfig has no mixer (models/ssm.py): it cannot run the configuration")
    from k8s_device_plugin_tpu.models.ssm import MambaConfig

    cfg = transformer.GPTConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        head_dim=model["head_dim"],
        intermediate_size=model["intermediate_size"],
        max_seq=engine["page_size"] * engine["max_pages_per_seq"],
        rope_theta=float(model["rope_theta"]),
        num_kv_heads=model["num_key_value_heads"],
        rms_norm_eps=model["rms_norm_eps"],
        logits_to_keep=model["num_logits_to_keep"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
        multipliers=transformer.Multipliers(
            embedding=model["embedding_multiplier"], lm_head=model["lm_head_multiplier"],
            attention_in=model["attention_in_multiplier"], key=model["key_multiplier"],
            attention_out=model["attention_out_multiplier"],
            ssm_in=model["ssm_in_multiplier"], ssm_out=model["ssm_out_multiplier"],
            mlp_gate=model["mlp_multipliers"][0], mlp_down=model["mlp_multipliers"][1],
        ),
        mixer=MambaConfig(
            d_ssm=model["mamba_d_ssm"], n_heads=model["mamba_n_heads"], head_dim=model["mamba_d_head"],
            d_state=model["mamba_d_state"], n_groups=model["mamba_n_groups"], d_conv=model["mamba_d_conv"],
            chunk_size=model["mamba_chunk_size"], in_multipliers=tuple(model["ssm_multipliers"]),
        ),
    )
    return cfg, transformer.PagedConfig(engine["page_size"], engine["num_pages"], engine["max_pages_per_seq"])


def params_tree(model: dict, seed_words):
    """The served tree in the program's layout (``TransformerLM`` params
    with a ``mixer`` subtree a layer), from the reference's own leaf
    functions: the same keys, shapes and stds.  The embedding and the head
    are written block by block into one buffer each, so that no float32
    copy of a 1.3 G-element leaf is ever alive."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import falcon_h1 as ref

    d = ref.dims(model)
    h, size = d["h"], ref.vocab_block(model)

    def blocks(make, shape, axis):
        def body(blk, out):
            start = (blk * size, 0) if axis == 0 else (0, blk * size)
            return jax.lax.dynamic_update_slice(out, make(model, seed_words, blk), start)

        return jax.lax.fori_loop(0, ref.VOCAB_BLOCKS, body, jnp.zeros(shape, jnp.bfloat16))

    tree = {
        "embed": {"embedding": blocks(ref.embed_block, (model["vocab_size"], h), 0)},
        "final_norm": {"scale": ref.final_norm(model, seed_words)},
        "lm_head": {"kernel": blocks(ref.head_block, (h, model["vocab_size"]), 1)},
    }
    for i in range(model["num_hidden_layers"]):
        w = ref.layer_leaves(model, seed_words, i)
        heads = {"query": d["nh"], "key": d["kv"], "value": d["kv"]}
        tree[f"layer_{i}"] = {
            "attn": {
                **{n: {"kernel": w[f"attn/{n}"].reshape(h, k, d["hd"])} for n, k in heads.items()},
                "out": {"kernel": w["attn/out"].reshape(d["nh"], d["hd"], h)},
            },
            "mixer": {
                "in_proj": {"kernel": jnp.concatenate([w[f"mixer/in_{s}"] for s in ("z", "x", "B", "C", "dt")], axis=1)},
                "conv_kernel": w["mixer/conv_kernel"], "conv_bias": w["mixer/conv_bias"],
                "dt_bias": w["mixer/dt_bias"], "A_log": w["mixer/A_log"], "D": w["mixer/D"],
                "norm_scale": w["mixer/norm"],
                "out_proj": {"kernel": w["mixer/out_proj"]},
            },
            "mlp": {n: {"kernel": w[f"mlp/{n}"]} for n in ("gate", "up", "down")},
            "attn_norm": {"scale": w["attn_norm"]},
            "mlp_norm": {"scale": w["mlp_norm"]},
        }
    return tree


def served_gaps(conf: dict, seed: int, cases: list[dict], pad_to: int, control: bool) -> list[dict]:
    from chipbench.reference import falcon_h1 as ref

    return ref.served_gaps(conf, seed, cases, pad_to, control=control)


# ------------------------------------------------------- counts of work ----
# From the shapes alone; called in the parent, which never imports JAX.


def layer_matmul_params(m: dict) -> int:
    """Parameters of one block's matrices: attention, the mixer's two
    projections, SwiGLU."""
    d = _dims(m)
    return d["h"] * (d["q"] + 2 * d["kv"]) + d["q"] * d["h"] + d["h"] * d["in"] + d["d_ssm"] * d["h"] + 3 * d["h"] * d["ff"]


def layer_vector_params(m: dict) -> int:
    """The convolution, its bias, dt_bias, A_log, D and the three norms."""
    d = _dims(m)
    return (m["mamba_d_conv"] + 1) * d["conv_dim"] + 3 * d["heads"] + d["d_ssm"] + 2 * d["h"]


def weight_bytes(m: dict) -> int:
    """Bytes a decode step reads of weights: every matrix, vector and the
    head once, bfloat16 (the embedding is a row lookup)."""
    layers = m["num_hidden_layers"] * (layer_matmul_params(m) + layer_vector_params(m))
    return 2 * (layers + m["hidden_size"] * m["vocab_size"] + m["hidden_size"])


def kv_bytes_per_token(m: dict) -> int:
    return 2 * m["num_hidden_layers"] * _dims(m)["kv"] * 2


def state_bytes_per_slot(m: dict, ctx: dict) -> float:
    """Bytes of one slot's recurrent state over all layers: the program's
    gauge ``tpu_engine_slot_state_bytes`` over the slots where the run
    scraped it, else from the shapes (float32 state, bfloat16 tail)."""
    gauge = ((ctx.get("scraped") or {}).get("after") or {}).get("tpu_engine_slot_state_bytes")
    if gauge and ctx.get("slots"):
        return gauge / ctx["slots"]
    d = _dims(m)
    return m["num_hidden_layers"] * (4 * d["state"] + 2 * (m["mamba_d_conv"] - 1) * d["conv_dim"])


def decode_state_bytes(m: dict, contexts: list[int], ctx: dict) -> float:
    """The state of every live slot read AND written once a step."""
    return 2.0 * state_bytes_per_slot(m, ctx) * len(contexts)


def token_flops(m: dict, pos: int, with_head: bool) -> float:
    """Forward FLOPs of one token at position ``pos``: two per parameter of
    every matrix, four per attended position and query unit, the
    recurrence's 4 H P N a layer (state update and read-out, a multiply and
    an add each), the head only where a logit is needed."""
    d = _dims(m)
    flops = 2.0 * m["num_hidden_layers"] * layer_matmul_params(m)
    flops += 4.0 * m["num_hidden_layers"] * (d["q"] * (pos + 1) + d["state"])
    if with_head:
        flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops


def request_flops(m: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Prefill of the prompt (the head at its last position only) and the
    decode steps that produce output tokens 2..n."""
    total = sum(token_flops(m, pos, pos == prompt_tokens - 1) for pos in range(prompt_tokens))
    return total + sum(token_flops(m, prompt_tokens + i - 1, True) for i in range(1, output_tokens))


def decode_step(m: dict, contexts: list[int], ctx: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots whose contexts hold
    ``contexts`` positions each: every weight once, each context's keys and
    values once and one token's written, each live slot's state read and
    written once."""
    flops = sum(token_flops(m, c, True) for c in contexts)
    nbytes = weight_bytes(m) + kv_bytes_per_token(m) * sum(c + 2 for c in contexts)
    return flops, float(nbytes + decode_state_bytes(m, contexts, ctx))
