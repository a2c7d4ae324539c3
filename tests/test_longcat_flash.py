"""Latent attention, the serving expert layer and the shortcut-connected
double-layer (models/mla.py, models/moe.py, ``ShortcutBlock``) on the paged
serving engine, at a tiny size on the CPU in float32: built by the
benchmark's family (chipbench/families/longcat_flash.py) and judged by its
plain reference (chipbench/reference/longcat_flash.py), which is float32 at
``highest``, expanded attention only, no cache.

The tiny preset (tests/chipbench/data/tiny-longcat-flash.json): 2
double-layers, 8 routed + 4 zero-computation experts, top-3, this replica
holds experts 0, 2 and 5."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import families, weights
from chipbench.reference import longcat_flash as ref
from k8s_device_plugin_tpu.models import mla, moe
from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.transformer import TransformerLM, decode_cache_spec
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

SEED = 13
with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-longcat-flash.json")) as f:
    MODEL = json.load(f)
GEOMETRY = {"page_size": 4, "num_pages": 96, "max_pages_per_seq": 16}
FAMILY = families.load("longcat_flash")
HELD = ref.held_experts(MODEL)
# Two buckets (16, 32), several lengths a bucket, one of a single chunk,
# one of four chunks, one that fills its bucket.
LENGTHS = [5, 9, 12, 16, 17, 23, 32]
NEW = 9  # a first token and two decode blocks of four


@pytest.fixture(scope="module")
def served():
    cfg, paged = FAMILY.build(MODEL, GEOMETRY)
    params = jax.jit(lambda words: FAMILY.params_tree(MODEL, words))(weights.seed_words(SEED))
    return cfg, paged, params


def make_engine(served, **kw):
    cfg, paged, params = served
    kw = {"max_slots": 4, "prefill_chunk": 8, "decode_block": 4, "admission": "optimistic", **kw}
    return ServingEngine(cfg, params, paged, **kw)


def prompt_of(n, salt=0):
    rng = np.random.default_rng(1000 * salt + n)
    return [int(t) for t in rng.integers(0, MODEL["vocab_size"], n)]


def series(registry, line_start):
    """The value of the one exposition line that starts so."""
    [line] = [l for l in registry.render().splitlines() if l.startswith(line_start + " ")]
    return float(line.rsplit(" ", 1)[1])


def assert_pad_lanes_zero(eng):
    """Every latent pool is stored lane-aligned, and the lanes past the
    model's row read zero on every page."""
    row = eng.cfg.mla.row_width
    for name in eng._layer_names:
        pool = np.asarray(eng.cache[name]["attn"]["pool_latent"])
        assert pool.shape[-1] % mla.LANES == 0 and pool.shape[-1] > row, pool.shape
        assert not pool[..., row:].any(), name


def worst_gap(cases, **kw):
    rows = ref.served_gaps(MODEL, SEED, cases, pad_to=48, control=False, **kw)
    return max(g for row in rows for g in row["gaps"])


def layer0(dtype=jnp.float32):
    w = jax.jit(lambda words: ref.layer_leaves(MODEL, words, 0, HELD))(weights.seed_words(SEED))
    return {k: v.astype(dtype) if v.dtype == jnp.bfloat16 else v for k, v in w.items()}


def some_hidden(seq, salt=0):
    return jax.random.normal(jax.random.PRNGKey(salt), (seq, MODEL["hidden_size"]), jnp.float32)


@pytest.fixture(scope="module")
def streams(served):
    """Every length served ONCE by one engine of four slots: admission
    groups of several lengths a bucket, chunked prefill, graft, decode
    blocks, slots reused."""
    registry = MetricsRegistry()
    eng = make_engine(served, metrics=EngineMetrics(registry))
    prompts = {n: prompt_of(n) for n in LENGTHS}
    done = eng.run([(prompts[n], NEW) for n in LENGTHS])
    return eng, registry, {n: {"prompt": prompts[n], "tokens": list(r.tokens)} for n, r in zip(LENGTHS, done)}


# ------------------------------------------------- (a) the served path ----


@pytest.fixture(params=["xla lane", "kernel through the interpreter"])
def expert_lane(request, monkeypatch):
    """Few tokens' experts on each lane of ops/expert_ffn.py: the XLA lane
    (what a CPU backend takes by itself) and the kernel forced through the
    Pallas interpreter."""
    if request.param != "xla lane":
        monkeypatch.setattr(moe, "expert_ffn", functools.partial(moe.expert_ffn, interpret=True))
    return request.param


def test_the_model_is_the_reference(served, expert_lane):
    """No cache, no engine: ``TransformerLM`` over one sequence (expanded
    attention, few tokens: every touched expert over all of them, on each
    lane) against the reference's logits."""
    cfg, _, params = served
    ids = np.asarray([prompt_of(29)], np.int32)
    got = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(ids)))[0]
    hidden, head = ref.forward_hidden(MODEL, SEED, ids)
    want = np.asarray(ref._matmul(hidden[None][0], head))
    assert np.abs(want).max() > 1.0, "the seeded stds must let the logits spread"
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("plen", LENGTHS)
def test_served_tokens_are_the_references_first(streams, plen):
    """Prefill (padded to the bucket, in chunks of 8, absorbed attention
    over the dense latent cache) then decode blocks through the paged
    latent pool: every served token is the reference's argmax but for
    float32 rounding."""
    _, _, cases = streams
    assert len(cases[plen]["tokens"]) == NEW
    assert worst_gap([cases[plen]]) < 1e-3


@pytest.mark.parametrize("part", ["experts", "attention_1"])
def test_the_comparison_sees_every_part(streams, part):
    """With the expert layer, or a pair's second attention, dropped from
    the reference the same tokens lie far from its argmax."""
    _, _, cases = streams
    assert worst_gap([cases[17], cases[23]], drop=part) > 0.1


def test_paged_decode_logits_are_the_full_forwards(served):
    """Teacher-forced through the paged latent cache a token at a time,
    after a two-chunk cached prefill: the logits of every position agree
    with the cache-less forward."""
    cfg, paged, params = served
    ids = jnp.asarray([prompt_of(22), prompt_of(22, salt=1)], jnp.int32)
    full = TransformerLM(cfg).apply({"params": params}, ids)
    model = TransformerLM(dataclasses.replace(cfg, paged=paged), decode=True, append_mode="cached")
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), decode_cache_spec(model, 2))
    table = jnp.arange(1, 1 + 2 * paged.max_pages_per_seq, dtype=jnp.int32).reshape(2, -1)
    cache = {k: {**v, "attn": {**v["attn"], "page_table": table}} for k, v in cache.items()}
    step = jax.jit(lambda c, t, p: model.apply({"params": params, "cache": c}, t, p, mutable=["cache"]))
    got, at = [], 0
    for n in (8, 8, 1, 1, 1, 1, 1, 1):
        logits, mut = step(cache, ids[:, at : at + n], jnp.broadcast_to(at + jnp.arange(n), (2, n)))
        cache, at = mut["cache"], at + n
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(full), rtol=2e-4, atol=2e-4)


# ------------------------------------------- (b) absorbed = expanded ----


def test_absorbed_attention_is_expanded_attention():
    """One attention of the tiny model: the module's cached append
    (absorbed, queries moved into the latent) and its cache-less call
    (expanded through kv_b) against the reference's expanded form."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, x = layer0(), some_hidden(19, salt=3)
    want = np.asarray(ref.attention(MODEL, w, "attn0", x))
    d = ref.dims(MODEL)
    params = {
        "q_a": {"kernel": w["attn0/q_a"]}, "q_norm": {"scale": w["attn0/q_norm"]},
        "q_b": {"kernel": w["attn0/q_b"].reshape(d["r_q"], d["H"], -1)},
        "kv_a": {"kernel": w["attn0/kv_a"]}, "kv_norm": {"scale": w["attn0/kv_norm"]},
        "kv_b": w["attn0/kv_b"].reshape(d["r_kv"], d["H"], -1),
        "out": {"kernel": w["attn0/o"].reshape(d["H"], d["d_v"], -1)},
    }
    pos = jnp.arange(19)[None]
    expanded = mla.LatentAttention(cfg).apply({"params": params}, x[None], pos)
    np.testing.assert_allclose(np.asarray(expanded[0]), want, rtol=1e-4, atol=1e-5)
    cached = mla.LatentAttention(dataclasses.replace(cfg, max_seq=24), decode=True, append_mode="cached")
    stored = cfg.mla.stored_width
    cache = {"cache_index": jnp.zeros((), jnp.int32), "cached_latent": jnp.zeros((1, 24, stored), jnp.float32)}
    absorbed, mut = cached.apply({"params": params, "cache": cache}, x[None], pos, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(absorbed[0]), want, rtol=1e-4, atol=1e-5)
    # What the cache holds of a token: the scaled normed latent and the
    # rotated key, 16 + 8 wide, then zero lanes up to 128.
    assert (cfg.mla.row_width, stored) == (24, 128)
    row = np.asarray(mut["cache"]["cached_latent"][0, :19])
    latent = ref._rmsnorm(ref._matmul(x, w["attn0/kv_a"])[:, :16], w["attn0/kv_norm"], 1e-5) * d["scale_kv"]
    np.testing.assert_allclose(row[:, :16], np.asarray(latent), rtol=1e-4, atol=1e-5)
    assert not row[:, 24:].any()


@pytest.mark.parametrize("kv_rank,rope_dim,stored", [(16, 8, 128), (512, 64, 640), (448, 64, 512), (120, 8, 128)])
def test_a_latent_row_is_stored_lane_aligned(kv_rank, rope_dim, stored):
    """The stored width is the row's rounded up to whole 128-lane tiles,
    read off the row alone: a row already aligned is stored as it is."""
    mc = mla.MlaConfig(kv_rank=kv_rank, rope_dim=rope_dim)
    assert mc.row_width == kv_rank + rope_dim and mc.stored_width == stored


def test_pad_lanes_reach_no_contraction():
    """Absorbed attention over rows with pad lanes is attention over the
    rows without them, whatever the lanes hold."""
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    q_n, q_r = jax.random.normal(keys[0], (2, 3, 4, 16)), jax.random.normal(keys[1], (2, 3, 4, 8))
    lat, kv_b = jax.random.normal(keys[2], (2, 12, 24)), jax.random.normal(keys[3], (16, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(9, 12), (2, 3))
    want = mla.absorbed_attention(q_n, q_r, lat, pos, kv_b, 0.2)
    padded = jnp.concatenate([lat, jax.random.normal(keys[4], (2, 12, 104))], axis=-1)
    np.testing.assert_array_equal(np.asarray(mla.absorbed_attention(q_n, q_r, padded, pos, kv_b, 0.2)), np.asarray(want))


def test_rows_taken_in_turn_give_the_same_attention(monkeypatch):
    """Past ``_SCORE_ELEMS`` score elements the rows of a batch are taken
    in blocks (lax.map): same numbers."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q_n, q_r = jax.random.normal(keys[0], (5, 3, 4, 16)), jax.random.normal(keys[1], (5, 3, 4, 8))
    lat, kv_b = jax.random.normal(keys[2], (5, 12, 24)), jax.random.normal(keys[3], (16, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(9, 12), (5, 3))
    whole = mla.absorbed_attention(q_n, q_r, lat, pos, kv_b, 0.2)
    monkeypatch.setattr(mla, "_SCORE_ELEMS", 2 * 4 * 3 * 12)  # two rows a block, a remainder of one
    np.testing.assert_allclose(np.asarray(mla.absorbed_attention(q_n, q_r, lat, pos, kv_b, 0.2)), np.asarray(whole), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------- the expert layer ----


def expert_params(w, idx):
    """The program's ``moe`` subtree from the reference's leaves, experts
    ``idx`` of its stacks."""
    idx = jnp.asarray(idx, jnp.int32)
    return {"router": w["moe/router"], "select_bias": w["moe/bias"],
            **{f"experts_{n}": w[f"moe/experts_{n}"][idx] for n in ("gate", "up", "down")}}


def expert_layer(cfg, w, u, held, mask=None):
    """The program's layer on one sequence, with the reference's leaves."""
    idx = [HELD.index(e) for e in held]
    params = expert_params(w, idx)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=tuple(held)))
    out, mut = moe.ExpertLayer(cfg).apply({"params": params}, u[None], mask, mutable=["moe_stats"])
    return out[0], dict(zip(moe.STATS, np.asarray(mut["moe_stats"]["counts"][0]))), np.asarray(mut["moe_stats"]["counts"][0])[len(moe.STATS):]


def test_an_experts_leaves_do_not_depend_on_its_neighbours():
    """What lets the shares add up: expert 5 has the same bits made alone,
    among the held three and among all eight."""
    words = weights.seed_words(SEED)
    alone = jax.jit(lambda w: ref.expert_leaves(MODEL, w, 1, 5))(words)
    for held in (HELD, tuple(range(8))):
        stacked = jax.jit(lambda w: ref.layer_leaves(MODEL, w, 1, held))(words)
        for name in ("gate", "up", "down"):
            assert jnp.array_equal(stacked[f"moe/experts_{name}"][held.index(5)], alone[name]), (held, name)


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(expert_lane):
    """(c) Four expert-parallel ranks of two experts each: the held
    experts' parts of all ranks, with the zero-computation experts (which
    every chip computes alike) counted ONCE, are the uncut reference
    layer over all eight experts; on each lane of the few-token experts."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    u, everyone = some_hidden(40, salt=5), tuple(range(8))
    leaves = jax.jit(lambda words: ref.layer_leaves(MODEL, words, 0, everyone))(weights.seed_words(SEED))
    w = {k: v.astype(jnp.float32) for k, v in leaves.items()}
    uncut = np.asarray(ref.expert_layer(MODEL, w, u, everyone))
    total = np.zeros_like(uncut)
    for rank in range(4):
        held = (2 * rank, 2 * rank + 1)
        w_rank = {**w, **{f"moe/experts_{n}": w[f"moe/experts_{n}"][jnp.asarray(held)] for n in ("gate", "up", "down")}}
        params = expert_params(w, held)
        rank_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=held))
        share = np.asarray(moe.ExpertLayer(rank_cfg).apply({"params": params}, u[None])[0])
        # The rank's share as the reference reckons it, then without the identity part.
        np.testing.assert_allclose(share, np.asarray(ref.expert_layer(MODEL, w_rank, u, held)), rtol=1e-4, atol=1e-5)
        identity = np.asarray(ref.expert_layer(MODEL, w_rank, u, (), identity=True))
        total += share - (identity if rank else 0)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-4)
    assert np.abs(uncut).max() > 0.5


def test_the_bias_changes_the_choice_and_not_the_weights():
    """(d) The selection bias is added for the choice only: the program
    agrees with the reference, dropping the bias from the reference breaks
    the agreement, and a chosen expert's weight is scaling x its UNBIASED
    probability."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, u = layer0(), some_hidden(64, salt=7)
    got, _, _ = expert_layer(cfg, w, u, HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.expert_layer(MODEL, w, u, HELD)), rtol=1e-4, atol=1e-5)
    unbiased = np.asarray(ref.expert_layer(MODEL, w, u, HELD, use_bias=False))
    assert np.abs(np.asarray(got) - unbiased).max() > 0.05
    ids, wt = ref.route(MODEL, w, u)
    ids0, _ = ref.route(MODEL, w, u, use_bias=False)
    changed = np.mean(np.sort(np.asarray(ids), -1) != np.sort(np.asarray(ids0), -1))
    assert changed > 0.05, "the seeded bias must change the choice for a visible share of tokens"
    p = np.asarray(jax.nn.softmax(jnp.matmul(u, w["moe/router"], precision="highest"), axis=-1))
    np.testing.assert_allclose(np.asarray(wt), MODEL["routed_scaling_factor"] * np.take_along_axis(p, np.asarray(ids), -1), rtol=1e-5)


@pytest.mark.parametrize("tokens,branch", [(24, "full: few tokens"), (384, "full: over the gathered rows"), (300, "gathered")])
def test_every_token_on_one_held_expert_drops_none(tokens, branch):
    """(e) A router that sends every token to held expert 2 first: nothing
    is dropped and the numbers are the reference's, with few tokens (an
    expert runs over all of them), with more than ``gather_rows`` on one
    expert (the full branch) and, routed evenly instead, through the
    gathered branch."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, u = layer0(), some_hidden(tokens, salt=tokens)
    if branch != "gathered":
        w = {**w, "moe/router": w["moe/router"].at[:, 2].set(0.0), "moe/bias": w["moe/bias"].at[2].set(10.0)}
    got, stats, per_expert = expert_layer(cfg, w, u, HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.expert_layer(MODEL, w, u, HELD)), rtol=2e-4, atol=2e-5)
    assert stats["dropped"] == 0 and stats["held"] + stats["identity"] + stats["absent"] == 3 * tokens
    if branch != "gathered":
        assert per_expert[HELD.index(2)] == tokens
        assert (tokens > moe.gather_rows(tokens)) == (tokens == 384)
    else:
        assert 0 < per_expert.max() <= moe.gather_rows(tokens) < tokens


def test_masked_tokens_route_nowhere_and_count_nothing():
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, u = layer0(), some_hidden(20, salt=9)
    mask = (jnp.arange(20) < 11)[None]
    got, stats, per_expert = expert_layer(cfg, w, u, HELD, mask)
    want = np.asarray(ref.expert_layer(MODEL, w, u[:11], HELD))
    np.testing.assert_allclose(np.asarray(got[:11]), want, rtol=1e-4, atol=1e-5)
    assert np.all(np.asarray(got[11:]) == 0)
    assert stats["held"] + stats["identity"] + stats["absent"] == 3 * 11 and stats["held"] == per_expert.sum()
    assert stats["touched"] == (per_expert > 0).sum() and stats["active"] == 1


# --------------------------------------------- (f) the cache layer ----


def test_graft_and_clear_are_one_dispatch_each_on_a_latent_pool(streams):
    eng, registry, _ = streams
    assert eng.cache_write_dispatches == {"graft": len(LENGTHS), "slot": len(LENGTHS)}
    assert eng.preemptions == 0 and eng.slot_state_bytes == 0 and eng._overlap_steps == 1
    # Optimistic admission grew generation pages through the chain writer.
    assert eng.chain_write_dispatches > 0 and eng.chain_pages_written >= len(LENGTHS)
    att = eng.cache["layer_0"]["attn"]
    assert sorted(att) == ["page_table", "pool_latent", "seq_lens"] and att["pool_latent"].shape == (96, 4, 128)
    # One float32 row of 16 + 8 an attention, four attentions; stored
    # 128 lanes wide, the other 104 padding.
    assert eng.cache_bytes_per_token == 4 * 24 * 4
    assert series(registry, "tpu_engine_cache_bytes_per_token") == eng.cache_bytes_per_token
    assert eng.cache_pad_bytes_per_token == 4 * (128 - 24) * 4
    assert series(registry, "tpu_engine_cache_pad_bytes_per_token") == eng.cache_pad_bytes_per_token
    state = eng.moe_state()
    assert state["latent_row"] == {"width": 24, "stored": 128}
    assert state["cache_pad_bytes_per_token"] == eng.cache_pad_bytes_per_token
    # After decode blocks, grafts and slot teardowns the pad lanes still read zero.
    assert_pad_lanes_zero(eng)


def test_the_routing_counts_come_back_with_the_tokens(streams):
    """Summed on the device over a decode block and unpacked from the
    block's one readback; the prefill chunks' at activation.  Every real
    token is counted once a layer, idle slots and padding never."""
    eng, registry, cases = streams
    state = eng.moe_state()
    prompt_tokens = sum(LENGTHS)
    decode_tokens = len(LENGTHS) * (NEW - 1)
    for phase, tokens in (("prefill", prompt_tokens), ("decode", decode_tokens)):
        got = state[phase]
        assert got["held"] + got["identity"] + got["absent"] == 2 * 3 * tokens, phase
        assert got["dropped"] == 0 and got["held"] == int(np.sum(got["expert_tokens"]))
    assert 0 < state["decode"]["touched"] <= 3 * state["decode"]["active"]
    for kind in ("held", "identity", "absent"):
        total = state["prefill"][kind] + state["decode"][kind]
        assert series(registry, f'tpu_engine_moe_assignments_total{{kind="{kind}"}}') == total
    assert series(registry, "tpu_engine_moe_identity_assignments_total") == state["prefill"]["identity"] + state["decode"]["identity"]
    assert series(registry, "tpu_engine_moe_dropped_assignments_total") == 0
    assert series(registry, "tpu_engine_moe_decode_layer_steps_total") == state["decode"]["active"]
    assert series(registry, "tpu_engine_moe_decode_experts_touched_total") == state["decode"]["touched"]
    per_expert = np.asarray(state["prefill"]["expert_tokens"]) + np.asarray(state["decode"]["expert_tokens"])
    assert series(registry, 'tpu_engine_moe_expert_tokens_total{expert="5",layer="1"}') == per_expert[1, 2]
    assert series(registry, "tpu_engine_moe_expert_tokens_peak") == per_expert.max()
    # The XLA lane on this backend: the kernel's counter exists and stays 0.
    assert state["expert_kernel"] is False and series(registry, "tpu_engine_moe_kernel_layer_steps_total") == 0


def test_the_kernels_counter_follows_the_lane_of_the_compiled_program(served, streams, monkeypatch):
    """On a backend whose lane is the kernel (here: said so to the engine,
    and the kernel forced through the interpreter) every decode layer-step
    is counted as the kernel's, the profile says ``expert_kernel``, and
    the served tokens are the XLA lane's."""
    from k8s_device_plugin_tpu.models import engine as engine_mod

    monkeypatch.setattr(engine_mod, "on_kernel_lane", lambda: True)
    monkeypatch.setattr(moe, "expert_ffn", functools.partial(moe.expert_ffn, interpret=True))
    registry = MetricsRegistry()
    eng = make_engine(served, metrics=EngineMetrics(registry))
    _, _, cases = streams
    done = eng.run([(cases[n]["prompt"], NEW) for n in (5, 17)])
    assert [list(r.tokens) for r in done] == [cases[n]["tokens"] for n in (5, 17)]
    state = eng.moe_state()
    assert state["expert_kernel"] is True and state["decode"]["active"] > 0 and state["decode"]["dropped"] == 0
    assert series(registry, "tpu_engine_moe_kernel_layer_steps_total") == state["decode"]["active"]
    assert series(registry, "tpu_engine_moe_decode_layer_steps_total") == state["decode"]["active"]


def test_a_shared_prefix_shares_latent_pages(served, streams):
    """Two prompts with a common first two pages, one after the other: the
    second rides the first's pages (the scheduler does not know what a
    page holds) and both are the reference's."""
    eng = make_engine(served, kv_retain=True)
    common = prompt_of(8, salt=4)
    jobs = [(common + prompt_of(5, salt=5), NEW), (common + prompt_of(7, salt=6), NEW)]
    first = eng.run(jobs[:1])
    second = eng.run(jobs[1:])
    assert eng.kv_retained_hits >= 2
    cases = [{"prompt": p, "tokens": list(r.tokens)} for (p, _), r in zip(jobs, first + second)]
    assert worst_gap(cases) < 1e-3


def test_preemption_resumes_on_a_latent_pool(served, streams):
    """Starve the pool so that growth preempts: the victim resumes (by
    recompute, or from its retained latent pages and a tail snapshot) and
    the streams are the undisturbed ones."""
    _, _, cases = streams
    eng = make_engine(served, max_slots=2, kv_retain=True, kv_host_cache_mb=8)
    with eng._lock:
        parked = [eng.free_pages.pop() for _ in range(len(eng.free_pages) - 8)]
    subs = [eng.submit(cases[n]["prompt"], NEW) for n in (9, 12)]
    for _ in range(4000):
        if all(r.done for r in subs):
            break
        eng.step()
    assert [r.tokens for r in subs] == [cases[n]["tokens"] for n in (9, 12)]
    assert eng.preemptions >= 1 and eng.kv_resumes_recompute + eng.kv_resumes_restored == eng.preemptions
    assert eng.moe_state()["decode"]["dropped"] == 0 and len(parked) > 0
    # The victim came back from its retained pages and a tail snapshot:
    # those rows went through the host and back with their pad lanes.
    assert eng.kv_resumes_restored >= 1
    assert_pad_lanes_zero(eng)


def test_a_snapshot_round_trip_restores_latent_pages(served, streams, tmp_path):
    """A warm replica's latent pages saved to a snapshot and loaded by a
    fresh one: the fresh one restores them from its host tier in place of
    a prefill, serves the same tokens, and its pad lanes read zero."""
    from k8s_device_plugin_tpu.models.engine_snapshot import load_arena_snapshot, save_arena_snapshot

    _, _, cases = streams
    prompt = cases[17]["prompt"]
    warm = make_engine(served, kv_retain=True, kv_host_cache_mb=8)
    [first] = warm.run([(prompt, NEW)])
    path = str(tmp_path / "kv_arena.snapshot")
    assert save_arena_snapshot(warm, path)["entries"] >= 4
    fresh = make_engine(served, kv_retain=True, kv_host_cache_mb=8)
    assert load_arena_snapshot(fresh, path)["restored"] >= 4
    [again] = fresh.run([(prompt, NEW)])
    assert first.tokens == again.tokens == cases[17]["tokens"]
    assert fresh.kv_restores >= 4
    assert_pad_lanes_zero(fresh)


def test_a_reused_slot_serves_what_a_fresh_one_serves(served, streams):
    _, _, cases = streams
    eng = make_engine(served, max_slots=1)
    for n in (23, 9, 5):
        [done] = eng.run([(cases[n]["prompt"], NEW)])
        assert done.tokens == cases[n]["tokens"]


# ------------------------------------------------------ (g) refusals ----


def test_paths_that_assume_key_and_value_pools_refuse(served):
    cfg, paged, params = served
    with pytest.raises(ValueError, match="spec_gamma.*latent attention"):
        make_engine(served, decode_block=1, spec_gamma=2, draft_params=params)
    for role in ("decode", "prefill"):
        with pytest.raises(ValueError, match=f"role='{role}'.*latent"):
            make_engine(served, kv_retain=True, kv_host_cache_mb=8, role=role)
    eng = make_engine(served, kv_retain=True, kv_host_cache_mb=8)
    with pytest.raises(ValueError, match="latent"):
        eng.set_role("decode")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="tp=2.*latent attention"):
        make_engine(served, mesh=mesh)
    with pytest.raises(ValueError, match="use_kernel.*latent attention"):
        ServingEngine(cfg, params, dataclasses.replace(paged, use_kernel=True), max_slots=2)
    for bad in ({"quant_kv": True}, {"quant": "w8"}):
        with pytest.raises(ValueError, match="quant"):
            ServingEngine(dataclasses.replace(cfg, **bad), params, paged, max_slots=2)
    with pytest.raises(ValueError, match="LoRA"):
        ServingEngine(dataclasses.replace(cfg, lora_rank=4, lora_serve=2), params, paged, max_slots=2)


def test_the_config_refuses_what_the_topology_cannot_hold():
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    with pytest.raises(ValueError, match="even"):
        dataclasses.replace(cfg, num_layers=3)
    with pytest.raises(ValueError, match="not among"):
        moe.MoeConfig(n_routed=8, held=(8,))
    with pytest.raises(ValueError, match="distinct"):
        moe.MoeConfig(n_routed=8, held=(1, 1))
    with pytest.raises(ValueError, match="mlp_factory"):
        TransformerLM(cfg, mlp_factory=lambda: None).init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
