"""A model with per-slot recurrent state (a Mamba-2 mixer beside attention
in every block, models/ssm.py) on the paged serving engine, at a tiny size
on the CPU in float32: built by the benchmark's family
(chipbench/families/falcon_h1.py) and judged by its plain reference
(chipbench/reference/falcon_h1.py).

The padding trap: a prompt is padded to its power-of-two bucket and run in
chunks; every prompt length of one bucket has to leave the state the
unpadded prompt leaves.  Then the cache layer: the state rides the two
compiled writers (one dispatch a graft, one a teardown), a reused slot
starts from zeros, a preempted request resumes by recompute and counts the
bypassed restore, and the paths that would skip the state's computation
refuse at construction."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import families, weights
from chipbench.reference import falcon_h1 as ref
from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.transformer import TransformerLM
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

SEED = 11
# The tiny cell's published keys: hidden 64, 2 layers, 4 mixer heads of 16,
# state 16, 2 groups, chunk 8, vocabulary 512, float32.
with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-falcon-h1.json")) as f:
    MODEL = json.load(f)
GEOMETRY = {"page_size": 4, "num_pages": 64, "max_pages_per_seq": 16}
FAMILY = families.load("falcon_h1")
# Every length of the bucket (8, 16], one of a single chunk, one of three
# chunks, one that fills its bucket of 32.
LENGTHS = list(range(9, 17)) + [5, 21, 32]
NEW = 7


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


def worst_gap(cases):
    rows = ref.served_gaps(MODEL, SEED, cases, pad_to=48, control=False)
    return max(g for row in rows for g in row["gaps"])


@pytest.fixture(scope="module")
def streams(served):
    """Every length served ONCE, by one engine of four slots: admission
    groups of several lengths a bucket, chunked prefill, graft, decode
    blocks, slots reused."""
    eng = make_engine(served)
    prompts = {n: prompt_of(n) for n in LENGTHS}
    done = eng.run([(prompts[n], NEW) for n in LENGTHS])
    return eng, {n: {"prompt": prompts[n], "tokens": list(r.tokens)} for n, r in zip(LENGTHS, done)}


def test_the_model_is_the_reference(served):
    """No cache, no engine: ``TransformerLM`` with the mixer over one
    sequence against the reference's logits."""
    cfg, _, params = served
    ids = np.asarray([prompt_of(29)], np.int32)
    got = np.asarray(jax.jit(TransformerLM(cfg).apply)({"params": params}, jnp.asarray(ids)))[0]
    hidden = ref.forward_hidden(MODEL, SEED, ids)[None][0]
    want = ref.logit_rows(MODEL, SEED, hidden)
    assert np.abs(want).max() > 1.0, "the seeded stds must let the logits spread"
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("plen", LENGTHS)
def test_served_tokens_are_the_references_first(streams, plen):
    """Prefill (padded to the bucket, in chunks of 8) then decode through
    the paged engine: every served token is the reference's argmax but for
    float32 rounding."""
    _, cases = streams
    assert len(cases[plen]["tokens"]) == NEW
    assert worst_gap([cases[plen]]) < 1e-3


def test_the_comparison_sees_the_mixer(streams):
    """With the mixer dropped from the reference the same tokens lie far
    from its argmax: the state matters to what is served."""
    _, cases = streams
    rows = ref.served_gaps(MODEL, SEED, [cases[16], cases[21]], pad_to=48, control=False, drop_mixer=True)
    assert max(g for row in rows for g in row["gaps"]) > 0.1


def test_graft_and_clear_are_one_dispatch_each(streams):
    eng, _ = streams
    assert eng.cache_write_dispatches == {"graft": len(LENGTHS), "slot": len(LENGTHS)}
    assert eng.preemptions == 0 and eng.slot_state_bytes > 0


def test_a_reused_slot_starts_from_zero_state(served, streams):
    """One slot, three requests one after another: each is served as the
    engine of four served it (there each started in another slot, beside
    other requests).  Between two occupants an idle slot keeps running
    through every decode step and gathers garbage in its state rows (the
    teardown's zeros do not last): the graft overwrites the whole row."""
    _, cases = streams
    eng = make_engine(served, max_slots=1)
    for n in (21, 12, 5):
        [done] = eng.run([(cases[n]["prompt"], NEW)])
        assert done.tokens == cases[n]["tokens"]


def test_staggered_finishes_never_advance_a_survivors_state_twice(served):
    """Single steps, two slots, one request ends ten tokens before the
    other.  With a dispatch in flight the finish would discard it, and
    the survivor's state would have taken that token twice (K/V writes
    are idempotent, a recurrence is not): gaps over 1 in the probe that
    found this.  The engine keeps such a model's loop synchronous."""
    eng = make_engine(served, decode_block=1, max_slots=2, overlap_steps=1)
    jobs = [(prompt_of(12), 4), (prompt_of(13), 14)]
    done = eng.run(jobs)
    assert eng.overlap_hits == eng.overlap_discards == 0
    assert worst_gap([{"prompt": p, "tokens": list(r.tokens)} for (p, _), r in zip(jobs, done)]) < 1e-3


def test_state_gauge_and_bytes(served):
    registry = MetricsRegistry()
    eng = make_engine(served, metrics=EngineMetrics(registry))
    per_slot = 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)  # two layers: float32 state, float32 tail (the tiny dtype)
    assert eng.slot_state_bytes == 4 * per_slot
    [line] = [l for l in registry.render().splitlines() if l.startswith("tpu_engine_slot_state_bytes ")]
    assert float(line.split()[-1]) == eng.slot_state_bytes
    assert eng._overlap_steps == 0  # a discarded dispatch could not roll the state back


def test_preemption_resumes_by_recompute_and_counts_the_bypass(served, streams):
    """Starve the pool so that growth preempts (as the kvcache suite does),
    with the retained tier and the host arena on: every resume had a
    snapshot, every one re-prefills (the snapshot holds no recurrent
    state), and the streams are the undisturbed ones."""
    _, cases = streams
    registry = MetricsRegistry()
    eng = make_engine(served, max_slots=2, kv_retain=True, kv_host_cache_mb=8, metrics=EngineMetrics(registry))
    with eng._lock:
        parked = [eng.free_pages.pop() for _ in range(len(eng.free_pages) - 8)]
    subs = [eng.submit(cases[n]["prompt"], NEW) for n in (13, 14)]
    for _ in range(4000):
        if all(r.done for r in subs):
            break
        eng.step()
    assert [r.tokens for r in subs] == [cases[n]["tokens"] for n in (13, 14)]
    assert eng.preemptions >= 1
    assert eng.kv_restore_resume_bypassed == eng.kv_resumes_recompute == eng.preemptions
    assert eng.kv_resumes_restored == 0
    assert eng.kvcache_state()["resumes"]["restore_bypassed"] == eng.preemptions
    assert f"tpu_engine_restore_resume_bypassed_total {eng.preemptions}" in registry.render()
    # A graft a first admission and a resume; a slot write a finish and a preemption.
    assert eng.cache_write_dispatches == {"graft": 2 + eng.preemptions, "slot": 2 + eng.preemptions}
    assert len(parked) > 0


def test_paths_that_skip_the_state_refuse(served):
    cfg, paged, params = served
    with pytest.raises(ValueError, match="spec_gamma.*recurrent state"):
        make_engine(served, decode_block=1, spec_gamma=2, draft_params=params)
    for role in ("decode", "prefill"):
        with pytest.raises(ValueError, match=f"role='{role}'.*recurrent state"):
            make_engine(served, kv_retain=True, kv_host_cache_mb=8, role=role)
    eng = make_engine(served, kv_retain=True, kv_host_cache_mb=8)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.set_role("decode")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="tp=2.*recurrent state"):
        make_engine(served, mesh=mesh)
