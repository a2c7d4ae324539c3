"""The tests' second family, added as files alone: ``TransformerLM`` as
``GPTConfig`` expresses it (so it runs on today's program), with leaves,
reference and count of work of its own (reference/toy_mixed.py)."""

from chipbench import families, flops

build = families.load("llm").build  # the same program; only the leaves differ


def params_tree(model: dict, seed_words):
    from chipbench.reference import toy_mixed as ref

    top = ref.top_leaves(model, seed_words)
    tree = {
        "embed": {"embedding": top["embed"]},
        "final_norm": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["lm_head"]},
    }
    for i in range(model["num_hidden_layers"]):
        leaf = ref.layer_leaves(model, seed_words, i)
        tree[f"layer_{i}"] = {
            "attn": {n: {"kernel": leaf[f"attn/{n}"]} for n in ("query", "key", "value", "out")},
            "mlp": {n: {"kernel": leaf[f"mlp/{n}"]} for n in ("gate", "up", "down")},
            "attn_norm": {"scale": leaf["attn_norm"]},
            "mlp_norm": {"scale": leaf["mlp_norm"]},
        }
    return tree


def served_gaps(conf: dict, seed: int, cases: list[dict], pad_to: int, control: bool) -> list[dict]:
    from chipbench.reference import toy_mixed as ref

    return ref.served_gaps(conf, seed, cases, pad_to, control=control)


def request_flops(model: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Its own count: two per parameter of the layers' matrices for every
    token that passes through them, and nothing else."""
    return 2.0 * flops.llm_matmul_params(model) * (prompt_tokens + output_tokens - 1)


def decode_step(model: dict, contexts: list[int], ctx: dict) -> tuple[float, float]:
    """Bytes that depend on what the run did: a counter of the run's."""
    f, b = flops.llm_decode_step(model, contexts)
    return f, b + ctx["scraped"]["after"].get("toy_mixed_extra_bytes", 0.0)
