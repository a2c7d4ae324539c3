"""The busiest held expert's tokens over the mean held expert's, all
expert layers together, over the replica's life to the window's end (the
warm-up's requests are random too): the gauge
``tpu_engine_moe_expert_tokens_peak`` over the mean series of
``tpu_engine_moe_expert_tokens_total`` (its sum over the held experts of
every expert layer, from the configuration).  1 is an even load.  Nothing to
read on a program without them."""


def read(ctx):
    after = ctx["scraped"]["after"]
    total = after.get("tpu_engine_moe_expert_tokens_total")
    if not total or "tpu_engine_moe_expert_tokens_peak" not in after:
        return None
    m = ctx["cell"].config["model"]
    return after["tpu_engine_moe_expert_tokens_peak"] / (total / (m["num_layers"] * m["n_routed_experts"]))
