"""The family of a dense Llama-shaped decoder (``TransformerLM`` as
``GPTConfig`` builds it: every layer of one kind, one leaf set a layer):
the ten published keys, ``weights.llm_params_tree``, ``reference/llm.py``
and ``flops.py``'s dense counts."""

from chipbench import flops

request_flops = flops.llm_request_flops


def build(model: dict, engine: dict):
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.transformer import GPTConfig, PagedConfig

    cfg = GPTConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        intermediate_size=model["intermediate_size"],
        max_seq=engine["page_size"] * engine["max_pages_per_seq"],
        rope_theta=float(model["rope_theta"]),
        num_kv_heads=model["num_key_value_heads"],
        attention_window=model.get("sliding_window"),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
    )
    return cfg, PagedConfig(engine["page_size"], engine["num_pages"], engine["max_pages_per_seq"])


def params_tree(model: dict, seed_words):
    from chipbench import weights

    return weights.llm_params_tree(model, seed_words)


def served_gaps(conf: dict, seed: int, cases: list[dict], pad_to: int, control: bool) -> list[dict]:
    from chipbench.reference import llm

    return llm.served_gaps(conf, seed, cases, pad_to, control=control)


def decode_step(model: dict, contexts: list[int], ctx: dict) -> tuple[float, float]:
    """Every weight read once a step, whatever the run did: ``ctx`` unused."""
    return flops.llm_decode_step(model, contexts)
