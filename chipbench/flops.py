"""Operations and bytes the algorithm needs, from a configuration's shapes.
Recomputed or wasted work (logits of prompt positions nobody reads, padding
to a bucket, a gathered copy of the cache) is not counted."""

from __future__ import annotations


def llm_layer_params(m: dict) -> int:
    """Parameters of one decoder layer's matrices."""
    h, ff = m["hidden_size"], m["intermediate_size"]
    hd = h // m["num_attention_heads"]
    kv = m["num_key_value_heads"] * hd
    return h * h + 2 * h * kv + h * h + 3 * h * ff


def llm_matmul_params(m: dict) -> int:
    """Parameters every token is multiplied by, the output head apart."""
    return m["num_hidden_layers"] * llm_layer_params(m)


def llm_head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def llm_weight_bytes(m: dict, bytes_per: int = 2) -> int:
    """Bytes a decode step reads of weights: every matrix and the head
    once (the embedding is a row lookup)."""
    norms = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
    return bytes_per * (llm_matmul_params(m) + llm_head_params(m) + norms)


def llm_kv_bytes_per_token(m: dict, bytes_per: int = 2) -> int:
    hd = m["hidden_size"] // m["num_attention_heads"]
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * hd * bytes_per


def _attended(pos: int, m: dict) -> int:
    """Cache positions a token at ``pos`` attends to (itself included)."""
    window = m.get("sliding_window")
    return min(pos + 1, window) if window else pos + 1


def llm_token_flops(m: dict, pos: int, with_head: bool) -> float:
    """Forward FLOPs of one token at position ``pos``: two per parameter of
    every matrix, four per attended position and hidden unit (scores and
    weighted sum, every head), the head only where a logit is needed."""
    flops = 2.0 * llm_matmul_params(m)
    flops += 4.0 * m["num_hidden_layers"] * m["hidden_size"] * _attended(pos, m)
    if with_head:
        flops += 2.0 * llm_head_params(m)
    return flops


def llm_request_flops(m: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Prefill of the prompt (the head at its last position only) and the
    decode steps that produce output tokens 2..n (the first comes from the
    prefill)."""
    total = 0.0
    for pos in range(prompt_tokens):
        total += llm_token_flops(m, pos, with_head=pos == prompt_tokens - 1)
    for i in range(1, output_tokens):
        total += llm_token_flops(m, prompt_tokens + i - 1, with_head=True)
    return total


def llm_decode_step(m: dict, context_tokens: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots whose contexts hold
    ``context_tokens`` positions each: weights read once, each context's
    keys and values read once, one token's K/V written per slot."""
    flops = sum(llm_token_flops(m, c, with_head=True) for c in context_tokens)
    kv = llm_kv_bytes_per_token(m)
    nbytes = llm_weight_bytes(m) + kv * sum(_attended(c, m) + 1 for c in context_tokens)
    return flops, float(nbytes)


# ----------------------------------------------------------------- ResNet


def resnet_convs(image: int = 224, width: int = 64, stages=(3, 4, 6, 3), classes: int = 1000):
    """Every convolution and the classifier of ResNet v1.5 as
    (out_h, out_w, k_h, k_w, c_in, c_out), SAME padding, stride 2 on the
    3x3 of each later stage's first block."""
    out = []
    size = -(-image // 2)
    out.append((size, size, 7, 7, 3, width))
    size = -(-size // 2)  # 3x3/2 max pool
    c_in = width
    for stage, blocks in enumerate(stages):
        f = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out.append((size, size, 1, 1, c_in, f))
            mid = -(-size // stride)
            out.append((mid, mid, 3, 3, f, f))
            out.append((mid, mid, 1, 1, f, 4 * f))
            if c_in != 4 * f or stride != 1:
                out.append((mid, mid, 1, 1, c_in, 4 * f))
            size, c_in = mid, 4 * f
    out.append((1, 1, 1, 1, c_in, classes))
    return out


def resnet_forward_flops(**kw) -> float:
    """Multiply-adds times two of one image's forward pass, conv by conv."""
    return float(sum(2 * oh * ow * kh * kw_ * ci * co for oh, ow, kh, kw_, ci, co in resnet_convs(**kw)))


def resnet_train_flops(**kw) -> float:
    """Forward plus backward (gradients to inputs and to weights: twice the
    forward), per image; the stem has no input gradient to make."""
    convs = resnet_convs(**kw)
    stem = 2 * convs[0][0] * convs[0][1] * convs[0][2] * convs[0][3] * convs[0][4] * convs[0][5]
    return 3.0 * resnet_forward_flops(**kw) - stem
