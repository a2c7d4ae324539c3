"""The gated delta rule with per-channel decay (models/kda.py) and its decode
kernel (ops/kda_decode.py) at small sizes on the CPU in float32: the
chunkwise form against the recurrence a token at a time, the kernel through
the Pallas interpreter against the jnp step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_device_plugin_tpu.models import kda
from k8s_device_plugin_tpu.ops import kda_decode


def inputs(b, t, heads, d, decay, salt=0):
    """q (scaled), k (l2-normed), v, g <= 0 and beta in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(salt), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, heads, d))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, heads, d)))
    v = jax.random.normal(ks[2], (b, t, heads, d))
    g = -decay * jax.random.uniform(ks[3], (b, t, heads, d), minval=0.5, maxval=1.5)
    beta = 2.0 * jax.random.uniform(ks[4], (b, t, heads))
    s0 = 0.3 * jax.random.normal(ks[5], (b, heads, d, d))
    return q, k, v, g, beta, s0


def recurrent(q, k, v, g, beta, s0):
    """S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T, o = S^T q,
    the d x d matrix a token, as written."""
    d = q.shape[-1]
    eye = jnp.eye(d)

    def token(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        house = eye - b_t[..., None, None] * k_t[..., :, None] * k_t[..., None, :]
        s = jnp.einsum("bhij,bhjv->bhiv", house, jnp.exp(g_t)[..., None] * s, precision="highest")
        s = s + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision="highest")

    swap = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    s_t, o = jax.lax.scan(token, s0, tuple(swap(x) for x in (q, k, v, g, beta)))
    return swap(o), s_t


@pytest.mark.parametrize("t_len,chunk", [(37, 16), (16, 16), (5, 4), (23, 1)])
@pytest.mark.parametrize("decay", [0.05, 60.0])
def test_the_chunkwise_form_is_the_recurrence(t_len, chunk, decay):
    """(b) Any length against any sub-chunk, a tail padded inside the form,
    from a state that is not zero; with mild decays and with decays so
    strong that exp(-cumsum(g)) over a sub-chunk overflows float32 (the
    form takes only differences of the cumulative log-decay)."""
    q, k, v, g, beta, s0 = inputs(2, t_len, 3, 8, decay, salt=t_len)
    want_o, want_s = recurrent(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(functools.partial(kda.kda_chunk, chunk=chunk))(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=2e-4, atol=2e-5)
    if decay > 1 and chunk > 1:
        naive = jnp.exp(-jnp.cumsum(g[:, :chunk], axis=1))
        assert not np.isfinite(np.asarray(naive)).all(), "these decays must overflow the naive form"


def test_positions_of_no_decay_and_no_write_leave_the_state_alone():
    """Padding as the mixer makes it (beta = 0, g = 0): the state after a
    padded sequence is the state after its real tokens."""
    q, k, v, g, beta, s0 = inputs(1, 20, 2, 8, 0.3, salt=3)
    real = 13
    pad = jnp.arange(20) >= real
    g_pad = jnp.where(pad[None, :, None, None], 0.0, g)
    beta_pad = jnp.where(pad[None, :, None], 0.0, beta)
    _, whole = kda.kda_chunk(q, k, v, g_pad, beta_pad, s0, 8)
    _, short = kda.kda_chunk(q[:, :real], k[:, :real], v[:, :real], g[:, :real], beta[:, :real], s0, 8)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(short), rtol=1e-5, atol=1e-6)


def step_inputs(b, heads, d, salt=0):
    q, k, v, g, beta, s0 = inputs(b, 1, heads, d, 0.2, salt=salt)
    return s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]


@pytest.mark.parametrize("slots,heads,d", [(3, 8, 16), (2, 32, 128), (1, 4, 8)])
def test_the_kernel_is_the_jnp_step(slots, heads, d, monkeypatch):
    """(c) Through the Pallas interpreter, with head blocks of 16 (or all
    heads) taken four at a time: the state and the output are the jnp
    step's but for the order of float32 sums, and the step is the
    recurrence's."""
    s0, q, k, v, g, beta = step_inputs(slots, heads, d, salt=heads)
    want_o, want_s = kda_decode.kda_step(s0, q, k, v, g, beta)
    got_o, got_s = kda_decode.kda_decode(s0, q, k, v, g, beta, interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-5, atol=1e-5)
    rec_o, rec_s = recurrent(*(x[:, None] for x in (q, k, v, g, beta)), s0)
    np.testing.assert_allclose(np.asarray(want_o), np.asarray(rec_o[:, 0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(want_s), np.asarray(rec_s), rtol=1e-4, atol=1e-5)


def test_shapes_that_do_not_belong_together_are_refused():
    s0, q, k, v, g, beta = step_inputs(2, 4, 8)
    with pytest.raises(ValueError):
        kda_decode.kda_decode(s0, q[:, :3], k, v, g, beta, interpret=True)
    with pytest.raises(ValueError):
        kda_decode.kda_decode(s0, q, k, v, g, beta[:, :1], interpret=True)
    with pytest.raises(ValueError):
        kda_decode.kda_decode(s0.astype(jnp.bfloat16), q, k, v, g, beta, interpret=True)


def test_the_lane_is_the_backends_and_one_token_a_row(monkeypatch):
    assert kda.kernel_lane(1) is False  # this backend is the CPU
    monkeypatch.setattr(kda, "on_kernel_lane", lambda: True)
    assert kda.kernel_lane(1) is True and kda.kernel_lane(4) is False


def test_a_programs_layers_share_one_lowered_kernel(monkeypatch):
    """A decode step of the tiny Solar Open 2 preset's three linear-attention
    layers on the kernel's lane lowers the kernel ONCE and calls it three
    times (one lowering a site adds seconds to a replica's set-up)."""
    import dataclasses
    import json
    import os

    from chipbench import families
    from k8s_device_plugin_tpu.models.transformer import TransformerLM, decode_cache_spec

    monkeypatch.setattr(kda, "on_kernel_lane", lambda: True)
    with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-solar-open2.json")) as f:
        model_conf = json.load(f)
    cfg, paged = families.load("solar_open2").build(model_conf, {"page_size": 4, "num_pages": 32, "max_pages_per_seq": 8})
    model = TransformerLM(dataclasses.replace(cfg, paged=paged), decode=True)
    ids = jnp.zeros((3, 1), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    cache = decode_cache_spec(model, 3)
    step = lambda p, c, t, pos: model.apply({"params": p, "cache": c}, t, pos, mutable=["cache"])  # noqa: E731
    text = jax.jit(step).lower(params, cache, ids, ids).as_text()
    assert text.count("func.func private @_call(") == 1
    assert text.count("call @_call(") == 3 == sum(cfg.linear_layer(i) for i in range(cfg.num_layers))
