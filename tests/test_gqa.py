"""Grouped-query attention: shapes, cache memory, decode parity, training."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

from k8s_device_plugin_tpu.models.train import create_train_state, make_train_step
from k8s_device_plugin_tpu.models.transformer import (
    GPTConfig,
    TransformerLM,
    greedy_generate,
)


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(GPTConfig.tiny(), num_kv_heads=2)  # 4 q heads / 2 kv


def test_gqa_param_and_cache_shapes(cfg):
    model = TransformerLM(cfg, decode=True)
    ids = jnp.zeros((2, 1), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids, jnp.zeros((2, 1), jnp.int32))
    attn = variables["params"]["layer_0"]["attn"]
    assert attn["query"]["kernel"].shape == (cfg.hidden_size, 4, cfg.head_dim)
    assert attn["key"]["kernel"].shape == (cfg.hidden_size, 2, cfg.head_dim)
    cache = variables["cache"]["layer_0"]["attn"]["cached_key"]
    assert cache.shape == (2, cfg.max_seq, 2, cfg.head_dim)  # kv heads, not q heads


def test_gqa_causality_and_finite(cfg):
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    ids_b = ids.at[0, -1].set((ids[0, -1] + 1) % cfg.vocab_size)
    logits_b = model.apply({"params": params}, ids_b)
    assert jnp.allclose(logits[:, :-1], logits_b[:, :-1], atol=1e-5)


def test_gqa_decode_matches_full_forward(cfg):
    model = TransformerLM(cfg)
    ids = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_size)
    out = greedy_generate(cfg, params, prompt, max_new_tokens=4)
    logits = model.apply({"params": params}, prompt)
    expect_first = jnp.argmax(logits[:, -1, :], axis=-1)
    assert jnp.array_equal(out[:, 6], expect_first)


def test_mqa_extreme_and_indivisible(cfg):
    # MQA (1 kv head) works end to end.
    mqa = dataclasses.replace(cfg, num_kv_heads=1)
    model = TransformerLM(mqa)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, mqa.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert bool(jnp.isfinite(model.apply({"params": params}, ids)).all())
    # Indivisible head grouping fails loudly.
    bad = dataclasses.replace(cfg, num_kv_heads=3)
    with pytest.raises(ValueError, match="not divisible"):
        TransformerLM(bad).init(jax.random.PRNGKey(0), ids)


@pytest.mark.slow  # composition blanket: training soak; GQA math stays pinned by test_gqa_decode_matches_full_forward and test_gqa_causality_and_finite
def test_gqa_trains(cfg):
    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (4, 16), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    tx = optax.adam(1e-2)
    state = create_train_state(rng, model, batch, tx, input_key="input_ids")
    step = jax.jit(make_train_step(model, tx, input_key="input_ids"))
    _, first = step(state, batch)
    for _ in range(8):
        state, loss = step(state, batch)
    assert float(loss) < float(first)


# ---- GQA-native kernel path (no jnp.repeat, kv tile shared) ----


def _rand_qkv(key, batch, heads, kv_heads, seq, dim, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, heads, seq, dim), dtype)
    k = jax.random.normal(kk, (batch, kv_heads, seq, dim), dtype)
    v = jax.random.normal(kv, (batch, kv_heads, seq, dim), dtype)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_flash_kernel_gqa_forward_parity(kv_heads):
    """flash_attention with un-expanded kv heads must equal repeat-then-MHA
    through mha_reference — through the kernel path, not a repeat shim."""
    from k8s_device_plugin_tpu.ops.flash_attention import (
        flash_attention,
        mha_reference,
    )

    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 4, kv_heads, 256, 64)
    got = flash_attention(q, k, v, causal=True)
    group = 4 // kv_heads
    k_rep = jnp.repeat(k, group, axis=1)
    v_rep = jnp.repeat(v, group, axis=1)
    want = mha_reference(q, k_rep, v_rep, causal=True)
    assert got.shape == q.shape
    assert jnp.allclose(got, want, atol=2e-3), float(jnp.abs(got - want).max())


def test_flash_kernel_gqa_backward_parity():
    """Gradients through the GQA kernel (custom chunked VJP) must match the
    plain-XLA repeat-then-MHA gradients for q, k, AND v — dK/dV must sum the
    whole head group's contribution onto the shared kv head."""
    from k8s_device_plugin_tpu.ops.flash_attention import (
        flash_attention,
        mha_reference,
    )

    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 4, 2, 256, 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        k_rep = jnp.repeat(k, 2, axis=1)
        v_rep = jnp.repeat(v, 2, axis=1)
        return jnp.sum(mha_reference(q, k_rep, v_rep, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_ref, "qkv"):
        assert got.shape == want.shape, name
        assert jnp.allclose(got, want, atol=5e-3), (
            name,
            float(jnp.abs(got - want).max()),
        )


def test_flash_kernel_gqa_with_window():
    """Sliding window + GQA compose in the kernel."""
    from k8s_device_plugin_tpu.ops.flash_attention import (
        flash_attention,
        mha_reference,
    )

    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 4, 2, 256, 32)
    got = flash_attention(q, k, v, causal=True, window=64)
    want = mha_reference(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
        causal=True, window=64,
    )
    assert jnp.allclose(got, want, atol=2e-3)


def test_flash_kernel_rejects_indivisible_heads():
    from k8s_device_plugin_tpu.ops.flash_attention import flash_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 4, 3, 128, 32)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v, causal=True)


def test_transformer_flash_path_carries_unexpanded_kv(cfg, monkeypatch):
    """The model's non-decode flash path must hand the kernel kv tensors with
    kv_heads (not num_heads) — proving the jnp.repeat is gone."""
    import k8s_device_plugin_tpu.models.transformer as tr

    seen = {}
    real = tr.flash_attention

    def spy(q, k, v, **kw):
        seen["q_heads"] = q.shape[1]
        seen["kv_heads"] = k.shape[1]
        return real(q, k, v, **kw)

    monkeypatch.setattr(tr, "flash_attention", spy)
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 128), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert bool(jnp.isfinite(logits).all())
    assert seen == {"q_heads": 4, "kv_heads": 2}
