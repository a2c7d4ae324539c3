"""The latent decode kernel (ops/latent_attention.py) through the Pallas
interpreter against the gather lane it replaces on a TPU: the pool's pages
gathered into the ``[batch, max_len, W]`` view, then
``mla.absorbed_attention``.  Then which lane ``LatentAttention`` takes:
the kernel for one query a row on a TPU backend, the gather for an append
of several and off the TPU.  ``PAGES_PER_BLOCK`` is set to two pages where
a slot's walk should cross blocks."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families
from k8s_device_plugin_tpu.models import mla
from k8s_device_plugin_tpu.models.transformer import TransformerLM, decode_cache_spec
from k8s_device_plugin_tpu.ops import latent_attention as kernel

PAGE, TABLE, NUM_PAGES = 16, 12, 64  # 192 addressable positions a slot
R_KV, D_R, D_N, D_V, HEADS, WIDTH = 32, 8, 16, 16, 4, 128  # key 40 wide, stored 128
SCALE = (D_N + D_R) ** -0.5
FULL = PAGE * TABLE


def case(lengths, dtype, seed=0, poison=True):
    """Queries, a pool and a shuffled, non-contiguous table for slots that
    see ``lengths`` rows.  With ``poison`` every pad lane, every row past a
    slot's length and every page no slot holds is NaN; the same case
    without it (those places 0) is what the gather lane reads."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    batch = len(lengths)
    q_n = jax.random.normal(keys[0], (batch, 1, HEADS, D_N), jnp.float32).astype(dtype)
    q_r = jax.random.normal(keys[1], (batch, 1, HEADS, D_R), jnp.float32).astype(dtype)
    kv_b = (jax.random.normal(keys[2], (R_KV, HEADS, D_N + D_V), jnp.float32) * R_KV ** -0.5).astype(dtype)
    rng = np.random.default_rng(seed)
    pages = rng.permutation(np.arange(1, NUM_PAGES))
    table = np.zeros((batch, TABLE), np.int32)
    live = np.zeros((NUM_PAGES, PAGE), bool)
    at = 0
    for b, n in enumerate(lengths):
        held = -(-n // PAGE)
        table[b, :held] = pages[at : at + held]
        for r in range(n):
            live[table[b, r // PAGE], r % PAGE] = True
        at += held
    rows = np.array(jax.random.normal(keys[3], (NUM_PAGES, PAGE, WIDTH), jnp.float32))
    rows[..., R_KV + D_R :] = np.nan if poison else 0.0
    rows[~live] = np.nan if poison else 0.0
    positions = jnp.asarray(np.asarray(lengths) - 1, jnp.int32)[:, None]
    return q_n, q_r, kv_b, jnp.asarray(rows, dtype), jnp.asarray(table), positions


def gather_lane(q_n, q_r, kv_b, pool, table, positions):
    view = pool[table].reshape(table.shape[0], FULL, WIDTH)
    return mla.absorbed_attention(q_n, q_r, view, positions, kv_b, SCALE)


def kernel_lane(q_n, q_r, kv_b, pool, table, positions):
    return jax.jit(lambda *a: mla.absorbed_decode(*a, kv_b, SCALE))(q_n, q_r, pool, table, positions)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, 127, 128, 129, FULL])
def test_the_kernel_is_the_gather_lane(length, dtype, monkeypatch):
    """A slot of ``length`` rows between two others (so the walk crosses
    slots in both directions), blocks of two pages: the kernel over a pool
    poisoned with NaN in every pad lane and every row past a length is the
    gather lane over the clean pool."""
    monkeypatch.setattr(kernel, "PAGES_PER_BLOCK", 2)
    lengths = [length, 37, max(1, FULL - length)]
    q_n, q_r, kv_b, poisoned, table, positions = case(lengths, dtype)
    _, _, _, clean, _, _ = case(lengths, dtype, poison=False)
    want = np.asarray(gather_lane(q_n, q_r, kv_b, clean, table, positions), np.float32)
    got = np.asarray(kernel_lane(q_n, q_r, kv_b, poisoned, table, positions), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("pages_per_block", [1, 3, None])
def test_any_block_of_pages_gives_the_same_attention(pages_per_block, monkeypatch):
    """Blocks of one page, of three (the table's width no multiple of it),
    and the default (the whole table here): the same numbers."""
    lengths = [100, 1, 16, 191, 33]
    q_n, q_r, kv_b, pool, table, positions = case(lengths, jnp.float32, seed=3)
    monkeypatch.setattr(kernel, "PAGES_PER_BLOCK", 2)
    want = np.asarray(kernel_lane(q_n, q_r, kv_b, pool, table, positions))
    if pages_per_block is not None:
        monkeypatch.setattr(kernel, "PAGES_PER_BLOCK", pages_per_block)
    else:
        monkeypatch.undo()
    got = np.asarray(kernel_lane(q_n, q_r, kv_b, pool, table, positions))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_an_idle_row_gives_a_finite_output(monkeypatch):
    """An idle slot: position 0, a table of zeros (scratch page 0, whose
    rows are finite), between two slots that decode."""
    q_n, q_r, kv_b, pool, table, positions = case([40, 1, 70], jnp.float32, seed=5, poison=False)
    table = table.at[1].set(0)
    pool = pool.at[0].set(jax.random.normal(jax.random.PRNGKey(9), (PAGE, WIDTH)).at[:, R_KV + D_R :].set(0.0))
    positions = positions.at[1, 0].set(0)
    monkeypatch.setattr(kernel, "PAGES_PER_BLOCK", 2)
    want = np.asarray(gather_lane(q_n, q_r, kv_b, pool, table, positions))
    got = np.asarray(kernel_lane(q_n, q_r, kv_b, pool, table, positions))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_shapes_that_do_not_belong_together_are_refused():
    q_lat = jnp.zeros((2, HEADS, WIDTH))
    pool, table, lens = jnp.zeros((8, PAGE, WIDTH)), jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="pool"):
        kernel.latent_decode_attention(q_lat, pool[..., :64], table, lens, key_width=40, kv_rank=32, sm_scale=1.0)
    with pytest.raises(ValueError, match="kv_rank"):
        kernel.latent_decode_attention(q_lat, pool, table, lens, key_width=200, kv_rank=32, sm_scale=1.0)


# ------------------------------------------------------- which lane ----


with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-longcat-flash.json")) as f:
    MODEL = json.load(f)


VIEW = "f32[3,8,4,128]"  # pool[table]: every addressable page of the three rows


def paged_attention_jaxpr(q_len):
    """The jaxpr of one paged append of ``q_len`` tokens a row through the
    tiny preset's first latent attention."""
    cfg, paged = families.load("longcat_flash").build(MODEL, {"page_size": 4, "num_pages": 32, "max_pages_per_seq": 8})
    att = mla.LatentAttention(dataclasses.replace(cfg, paged=paged), decode=True)
    x, pos = jnp.zeros((3, q_len, cfg.hidden_size)), jnp.zeros((3, q_len), jnp.int32)
    variables = jax.eval_shape(lambda: att.init(jax.random.PRNGKey(0), x, pos))
    return str(jax.make_jaxpr(lambda v: att.apply(v, x, pos, mutable=["cache"]))(variables))


def test_off_the_tpu_a_decode_step_takes_the_gather_lane():
    assert not mla.latent_kernel_lane(1), "the tests run on the CPU"
    text = paged_attention_jaxpr(1)
    assert "pallas_call" not in text and VIEW in text


def test_an_append_of_several_takes_the_gather_lane(monkeypatch):
    """Said to be on a TPU: a decode step runs the kernel and no gather; an
    append of three tokens a row still gathers."""
    monkeypatch.setattr(mla, "on_kernel_lane", lambda: True)
    assert mla.latent_kernel_lane(1) and not mla.latent_kernel_lane(3)
    one = paged_attention_jaxpr(1)
    assert "pallas_call" in one and "latent_decode_attention" in one and VIEW not in one
    several = paged_attention_jaxpr(3)
    assert "pallas_call" not in several and VIEW in several


def test_a_programs_attentions_share_one_lowered_kernel(monkeypatch):
    """A decode step of the tiny preset's four latent attentions on the
    kernel's lane lowers the kernel ONCE and calls it four times (lowered
    once an attention, eight of them added seconds to every replica's
    set-up on the chip's host)."""
    monkeypatch.setattr(mla, "on_kernel_lane", lambda: True)
    cfg, paged = families.load("longcat_flash").build(MODEL, {"page_size": 4, "num_pages": 32, "max_pages_per_seq": 8})
    model = TransformerLM(dataclasses.replace(cfg, paged=paged), decode=True)
    ids = jnp.zeros((3, 1), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    cache = decode_cache_spec(model, 3)
    step = lambda p, c, t, pos: model.apply({"params": p, "cache": c}, t, pos, mutable=["cache"])  # noqa: E731
    text = jax.jit(step).lower(params, cache, ids, ids).as_text()
    assert text.count("func.func private @_call(") == 1
    assert text.count("call @_call(") == cfg.num_layers == 4
