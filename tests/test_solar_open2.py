"""Linear-attention layers beside a softmax layer without positions, and an
expert layer with a shared expert in every layer (models/kda.py, ``GPTConfig``
``kda``/``attention_layers``/``use_rope``/``attn_gate``, ``MoeConfig``
``shared_size``/``score``/``norm_topk``) on the paged serving engine, at a
tiny size on the CPU in float32: built by the benchmark's family
(chipbench/families/solar_open2.py) and judged by its plain reference
(chipbench/reference/solar_open2.py), which is float32 at ``highest``, the
linear attention's recurrence a token at a time, no cache.

The tiny preset (tests/chipbench/data/tiny-solar-open2.json): one period of
4 layers (softmax attention in layer 0, linear attention in 1-3), heads of
16, 16 routed experts of which this replica holds 8, top-4, one shared
expert."""

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
from chipbench.reference import solar_open2 as ref
from k8s_device_plugin_tpu.models import kda, mla, moe, transformer
from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.transformer import TransformerLM, decode_cache_spec
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

SEED = 13
with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-solar-open2.json")) as f:
    MODEL = json.load(f)
GEOMETRY = {"page_size": 4, "num_pages": 96, "max_pages_per_seq": 16}
FAMILY = families.load("solar_open2")
HELD = ref.held_experts(MODEL)
# Two buckets (16, 32), several lengths a bucket, one of a single chunk,
# one of four chunks, one that fills its bucket: padding in the bucket.
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


def worst_gap(cases, **kw):
    rows = ref.served_gaps(MODEL, SEED, cases, pad_to=48, control=False, **kw)
    return max(g for row in rows for g in row["gaps"])


def serve_all(served):
    registry = MetricsRegistry()
    eng = make_engine(served, metrics=EngineMetrics(registry))
    prompts = {n: prompt_of(n) for n in LENGTHS}
    done = eng.run([(prompts[n], NEW) for n in LENGTHS])
    return eng, registry, {n: {"prompt": prompts[n], "tokens": list(r.tokens)} for n, r in zip(LENGTHS, done)}


@pytest.fixture(scope="module")
def streams(served):
    """Every length served ONCE by one engine of four slots: admission
    groups of several lengths a bucket, chunked prefill, graft, decode
    blocks, slots reused."""
    return serve_all(served)


@pytest.fixture(scope="module")
def kernel_streams(served):
    """The same jobs served by an engine on the recurrence kernel's lane
    (said to be on a TPU; the kernel through the Pallas interpreter)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "on_kernel_lane", lambda: True)
        return serve_all(served)


def layer_leaves(layer, held=HELD):
    w = jax.jit(lambda words: ref.layer_leaves(MODEL, words, layer, held))(weights.seed_words(SEED))
    return {k: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v for k, v in w.items()}


def some_hidden(seq, salt=0):
    return jax.random.normal(jax.random.PRNGKey(salt), (seq, MODEL["hidden_size"]), jnp.float32)


# ------------------------------------------------- (a) the served path ----


@pytest.fixture(params=["xla lane", "kernel through the interpreter"])
def expert_lane(request, monkeypatch):
    """Few tokens' experts on each lane of ops/expert_ffn.py."""
    if request.param != "xla lane":
        monkeypatch.setattr(moe, "expert_ffn", functools.partial(moe.expert_ffn, interpret=True))
    return request.param


def test_the_model_is_the_reference(served, expert_lane):
    """No cache, no engine: ``TransformerLM`` over one sequence (the
    chunkwise form from a zero state) against the reference's logits."""
    cfg, _, params = served
    ids = np.asarray([prompt_of(29)], np.int32)
    got = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(ids)))[0]
    hidden, head = ref.forward_hidden(MODEL, SEED, ids)
    want = np.asarray(ref._matmul(hidden[None][0], head))
    assert np.abs(want).max() > 1.0, "the seeded stds must let the logits spread"
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("plen", LENGTHS)
def test_served_tokens_are_the_references_first(streams, plen):
    """Prefill (padded to the bucket, in chunks of 8: pages for layer 0,
    state for layers 1-3) then decode blocks through the mixed cache:
    every served token is the reference's argmax but for float32
    rounding."""
    _, _, cases = streams
    assert len(cases[plen]["tokens"]) == NEW
    assert worst_gap([cases[plen]]) < 1e-3


@pytest.mark.parametrize("plen", LENGTHS)
def test_served_tokens_on_the_recurrence_kernel_are_the_references_first(kernel_streams, plen):
    """As above, with every decode step's recurrence run by the kernel."""
    _, _, cases = kernel_streams
    assert len(cases[plen]["tokens"]) == NEW
    assert worst_gap([cases[plen]]) < 1e-3


@pytest.mark.parametrize("part", ["recurrence", "attention"])
def test_the_comparison_sees_every_part(streams, part):
    """With the linear attention's memory, or the softmax layer, dropped
    from the reference the same tokens lie far from its argmax."""
    _, _, cases = streams
    assert worst_gap([cases[17], cases[23]], drop=part) > 0.1


@pytest.mark.parametrize("lane", ["jnp step", "kernel through the interpreter"])
def test_paged_decode_logits_are_the_full_forwards(served, lane, monkeypatch):
    """Teacher-forced through the mixed cache a token at a time, after a
    two-chunk cached prefill: the logits of every position agree with the
    cache-less forward."""
    if lane != "jnp step":
        monkeypatch.setattr(kda, "on_kernel_lane", lambda: True)
    cfg, paged, params = served
    ids = jnp.asarray([prompt_of(22), prompt_of(22, salt=1)], jnp.int32)
    full = TransformerLM(cfg).apply({"params": params}, ids)
    model = TransformerLM(dataclasses.replace(cfg, paged=paged), decode=True, append_mode="cached")
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), decode_cache_spec(model, 2))
    table = jnp.arange(1, 1 + 2 * paged.max_pages_per_seq, dtype=jnp.int32).reshape(2, -1)
    cache = {k: {**v, "attn": {**v["attn"], "page_table": table}} if "attn" in v else v for k, v in cache.items()}
    step = jax.jit(lambda c, t, p: model.apply({"params": params, "cache": c}, t, p, mutable=["cache"]))
    got, at = [], 0
    for n in (8, 8, 1, 1, 1, 1, 1, 1):
        logits, mut = step(cache, ids[:, at : at + n], jnp.broadcast_to(at + jnp.arange(n), (2, n)))
        cache, at = mut["cache"], at + n
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(full), rtol=2e-4, atol=2e-4)


# ------------------------------------------------- the expert layer ----


def expert_params(w, idx):
    return {
        "router": w["moe/router"], "select_bias": w["moe/bias"],
        **{f"experts_{n}": w[f"moe/experts_{n}"][jnp.asarray(idx)] for n in ("gate", "up", "down")},
        **{n: w[f"moe/{n}"] for n in ("shared_gate", "shared_up", "shared_down")},
    }


def expert_layer(cfg, w, u, held, mask=None):
    """The program's layer on one sequence, with the reference's leaves."""
    params = expert_params(w, [HELD.index(e) for e in held])
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=tuple(held)))
    out, mut = moe.ExpertLayer(cfg).apply({"params": params}, u[None], mask, mutable=["moe_stats"])
    counts = np.asarray(mut["moe_stats"]["counts"][0])
    return out[0], dict(zip(moe.STATS, counts)), counts[len(moe.STATS):]


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(expert_lane):
    """(d) Two expert-parallel ranks of eight experts each: the held
    experts' parts of both ranks, with the shared expert (which every chip
    computes alike) counted ONCE, are the uncut reference layer over all
    sixteen experts; on each lane of the few-token experts."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    u, everyone = some_hidden(40, salt=5), tuple(range(16))
    w = layer_leaves(1, everyone)
    uncut = np.asarray(ref.expert_layer(MODEL, w, u, everyone))
    shared = np.asarray(ref.expert_layer(MODEL, w, u, ()))
    total = np.zeros_like(uncut)
    for rank in range(2):
        held = tuple(range(8 * rank, 8 * rank + 8))
        params = expert_params(w, held)
        rank_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=held))
        share = np.asarray(moe.ExpertLayer(rank_cfg).apply({"params": params}, u[None])[0])
        w_rank = {**w, **{f"moe/experts_{n}": w[f"moe/experts_{n}"][jnp.asarray(held)] for n in ("gate", "up", "down")}}
        np.testing.assert_allclose(share, np.asarray(ref.expert_layer(MODEL, w_rank, u, held)), rtol=1e-4, atol=1e-5)
        total += share - (shared if rank else 0)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-4)
    assert np.abs(uncut - shared).max() > 0.05, "the routed experts must add something visible"


def test_the_bias_changes_the_choice_and_not_the_weights():
    """(e) The selection bias is added for the choice only: the program
    agrees with the reference, a chosen expert's weight is its UNBIASED
    sigmoid score renormalised over the chosen, and the seeded bias
    changes the choice for a visible share of tokens."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, u = layer_leaves(2), some_hidden(64, salt=7)
    got, _, _ = expert_layer(cfg, w, u, HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.expert_layer(MODEL, w, u, HELD)), rtol=1e-4, atol=1e-5)
    ids, wt = ref.route(MODEL, w, u)
    ids0, _ = ref.route(MODEL, w, u, use_bias=False)
    changed = np.mean(np.any(np.sort(np.asarray(ids), -1) != np.sort(np.asarray(ids0), -1), axis=-1))
    assert changed > 0.05, "the seeded bias must change the choice for a visible share of tokens"
    s = np.asarray(jax.nn.sigmoid(jnp.matmul(u, w["moe/router"], precision="highest")))
    chosen = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(wt), chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # Under a bias that flips the choice, the program moves with the reference.
    strong = {**w, "moe/bias": w["moe/bias"].at[jnp.asarray(HELD[:2])].set(1.0)}
    got_strong, stats, per_expert = expert_layer(cfg, strong, u, HELD)
    np.testing.assert_allclose(np.asarray(got_strong), np.asarray(ref.expert_layer(MODEL, strong, u, HELD)), rtol=1e-4, atol=1e-5)
    assert per_expert[0] == per_expert[1] == 64 and stats["held"] == per_expert.sum()


@pytest.mark.parametrize("tokens,branch", [(24, "full: few tokens"), (384, "full: over the gathered rows"), (300, "gathered")])
def test_every_token_on_one_held_expert_drops_none(tokens, branch):
    """(f) A bias that sends every token to held expert 3: nothing is
    dropped and the numbers are the reference's, with few tokens, with
    more than ``gather_rows`` on one expert and, routed as seeded, through
    the gathered branch."""
    cfg, _ = FAMILY.build(MODEL, GEOMETRY)
    w, u = layer_leaves(0), some_hidden(tokens, salt=tokens)
    if branch != "gathered":
        w = {**w, "moe/bias": w["moe/bias"].at[3].set(10.0)}
    got, stats, per_expert = expert_layer(cfg, w, u, HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.expert_layer(MODEL, w, u, HELD)), rtol=2e-4, atol=2e-5)
    assert stats["dropped"] == 0 and stats["identity"] == 0 and stats["held"] + stats["absent"] == 4 * tokens
    if branch != "gathered":
        assert per_expert[HELD.index(3)] == tokens
        assert (tokens > moe.gather_rows(tokens)) == (tokens == 384)
    else:
        assert 0 < per_expert.max() <= moe.gather_rows(tokens) < tokens


# --------------------------------------------- (g) the cache layer ----


def test_state_layers_hold_no_pages(streams):
    """Layer 0 holds K/V pages and nothing else; layers 1-3 hold their slot
    state and no ``attn`` subtree.  Pages are counted for layer 0 only."""
    eng, registry, _ = streams
    assert sorted(eng.cache) == [f"layer_{i}" for i in range(4)] and eng._layer_names == ["layer_0"]
    assert list(eng.cache["layer_0"]) == ["attn"]
    assert sorted(eng.cache["layer_0"]["attn"]) == ["page_table", "pool_key", "pool_value", "seq_lens"]
    for i in (1, 2, 3):
        state = eng.cache[f"layer_{i}"]
        assert list(state) == ["kda"] and state["kda"]["slot_kda"].shape == (4, 4, 16, 16)
        assert state["kda"]["slot_kda_conv"].shape == (4, 3, 3 * 64)
    # K and V of 2 heads of 16, float32 (the tiny dtype), one layer.
    assert eng.cache_bytes_per_token == 2 * 2 * 16 * 4 == series(registry, "tpu_engine_cache_bytes_per_token")
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert eng.slot_state_bytes == 4 * per_slot == series(registry, "tpu_engine_slot_state_bytes")
    assert eng._overlap_steps == 0  # a discarded dispatch could not roll the state back
    assert eng.kda_state() == {"layers": 3, "kernel": False, "slot_state_bytes": 4 * per_slot,
                               "cache_bytes_per_token": 256}


def test_graft_and_clear_are_one_dispatch_each(streams):
    eng, _, _ = streams
    assert eng.cache_write_dispatches == {"graft": len(LENGTHS), "slot": len(LENGTHS)}
    assert eng.preemptions == 0 and eng.chain_write_dispatches > 0


def test_the_counts_come_back_with_the_tokens(streams):
    """Routing counts: every real token counted once a layer, idle slots
    and padding never; the linear layers' decode steps counted three a
    step, none of them the kernel's on this backend."""
    eng, registry, _ = streams
    state = eng.moe_state()
    for phase, tokens in (("prefill", sum(LENGTHS)), ("decode", len(LENGTHS) * (NEW - 1))):
        got = state[phase]
        assert got["held"] + got["absent"] == 4 * 4 * tokens and got["identity"] == 0, phase
        assert got["dropped"] == 0 and got["held"] == int(np.sum(got["expert_tokens"]))
    steps = series(registry, "tpu_engine_kda_decode_layer_steps_total")
    assert steps > 0 and steps % 3 == 0
    assert series(registry, "tpu_engine_kda_kernel_layer_steps_total") == 0
    assert series(registry, "tpu_engine_moe_dropped_assignments_total") == 0


def test_the_kernels_counter_follows_the_lane_of_the_compiled_program(streams, kernel_streams):
    """On the kernel's lane every (decode step, linear layer) pair is the
    kernel's, the profile says so, and the served tokens are the jnp
    step's."""
    eng, registry, cases = kernel_streams
    assert [c["tokens"] for c in cases.values()] == [c["tokens"] for c in streams[2].values()]
    steps = series(registry, "tpu_engine_kda_decode_layer_steps_total")
    assert steps > 0 and series(registry, "tpu_engine_kda_kernel_layer_steps_total") == steps
    assert eng.kda_state()["kernel"] is True


def test_a_reused_slot_starts_from_zero_state(served, streams):
    """One slot, three requests one after another: each is served as the
    engine of four served it.  Between occupants an idle slot's state rows
    gather garbage through every decode step: the graft overwrites them."""
    _, _, cases = streams
    eng = make_engine(served, max_slots=1)
    for n in (23, 9, 5):
        [done] = eng.run([(cases[n]["prompt"], NEW)])
        assert done.tokens == cases[n]["tokens"]


def test_a_shared_prefix_shares_pages_and_recomputes_the_state(served):
    """Two prompts with a common first two pages, one after the other: the
    second rides the first's pages in layer 0 (the scheduler does not know
    what a page holds), its linear-attention state is rebuilt from the
    whole prompt, and both are the reference's."""
    eng = make_engine(served, kv_retain=True)
    common = prompt_of(8, salt=4)
    jobs = [(common + prompt_of(5, salt=5), NEW), (common + prompt_of(7, salt=6), NEW)]
    first = eng.run(jobs[:1])
    second = eng.run(jobs[1:])
    assert eng.kv_retained_hits >= 2
    cases = [{"prompt": p, "tokens": list(r.tokens)} for (p, _), r in zip(jobs, first + second)]
    assert worst_gap(cases) < 1e-3


def test_preemption_resumes_by_recompute(served, streams):
    """Starve the pool so that growth preempts, with the retained tier and
    the host arena on: every resume re-prefills (a snapshot holds no
    state), counted, and the streams are the undisturbed ones."""
    _, _, cases = streams
    registry = MetricsRegistry()
    eng = make_engine(served, max_slots=2, kv_retain=True, kv_host_cache_mb=8, metrics=EngineMetrics(registry))
    with eng._lock:
        parked = [eng.free_pages.pop() for _ in range(len(eng.free_pages) - 8)]
    subs = [eng.submit(cases[n]["prompt"], NEW) for n in (9, 12)]
    for _ in range(4000):
        if all(r.done for r in subs):
            break
        eng.step()
    assert [r.tokens for r in subs] == [cases[n]["tokens"] for n in (9, 12)]
    assert eng.preemptions >= 1 and len(parked) > 0
    assert eng.kv_restore_resume_bypassed == eng.kv_resumes_recompute == eng.preemptions
    assert f"tpu_engine_restore_resume_bypassed_total {eng.preemptions}" in registry.render()
    assert eng.moe_state()["decode"]["dropped"] == 0


# ------------------------------------------------------ (h) refusals ----


def test_paths_that_skip_the_state_refuse(served):
    cfg, paged, params = served
    with pytest.raises(ValueError, match="spec_gamma.*cfg.kda"):
        make_engine(served, decode_block=1, spec_gamma=2, draft_params=params)
    for role in ("decode", "prefill"):
        with pytest.raises(ValueError, match=f"role='{role}'.*cfg.kda"):
            make_engine(served, kv_retain=True, kv_host_cache_mb=8, role=role)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="tp=2.*cfg.kda"):
        make_engine(served, mesh=mesh)
    for bad in ({"quant": "w8"}, {"lora_rank": 4, "lora_serve": 2}):
        with pytest.raises(ValueError, match="quant or LoRA"):
            ServingEngine(dataclasses.replace(cfg, **bad), params, paged, max_slots=2)


def test_the_config_refuses_what_it_cannot_build(served):
    cfg, paged, params = served
    with pytest.raises(ValueError, match="needs one layer with attention"):
        ServingEngine(dataclasses.replace(cfg, attention_layers=()), params, paged, max_slots=2)
    with pytest.raises(ValueError, match="not among the 4 layers"):
        dataclasses.replace(cfg, attention_layers=(4,))
    with pytest.raises(ValueError, match="beside cfg.mla or cfg.mixer"):
        dataclasses.replace(cfg, mla=mla.MlaConfig())
    with pytest.raises(ValueError, match="which is not set"):
        dataclasses.replace(cfg, kda=None)
    with pytest.raises(ValueError, match="softmax\\|sigmoid"):
        moe.MoeConfig(n_routed=16, n_zero=0, top_k=4, held=(0,), score="relu")
    with pytest.raises(ValueError, match="comes with cfg.moe"):
        transformer.GPTConfig(mla=mla.MlaConfig())
