"""A prefill job's zero dense cache (models/engine_admission.py): ONE
dispatch of one compiled program of no operands per (bucket, batch),
whatever the leaves of the tree.

Two models at a toy size on the CPU in float32, each built by its
benchmark family and judged by that family's plain reference, as
tests/test_engine_state.py does: a dense decoder of three layers (K, V and
an index a layer) and Falcon-H1's block (a mixer's state and convolution
tail beside them), each served chunked and unchunked.  ``served`` plays
the same rounds through each engine and records every ``_start_prefill``
call, so the counters are set against what was admitted."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench import families, weights
from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

SEED = 31
DATA = os.path.join(os.path.dirname(__file__), "chipbench", "data")
GEOMETRY = {"page_size": 4, "num_pages": 64, "max_pages_per_seq": 16}
NEW = 5
# Rounds of prompt lengths, each submitted together to an idle engine of
# four slots: a round is one admission pass, a bucket of it one group.
# Buckets 8 and 16 (and 32, three chunks of 8), groups of 1, 2 and 4 rows;
# (8, 2) and (16, 1) are met three times each.
ROUNDS = [[5, 7], [12], [6, 13, 8], [7, 5], [9], [5, 8, 6], [21]]
GROUPS = [(8, 2), (16, 1), (8, 2), (16, 1), (8, 2), (16, 1), (8, 4), (32, 1)]


def model_of(kind):
    name = {"dense": "tiny-llm.json", "mixer": "tiny-falcon-h1.json"}[kind]
    with open(os.path.join(DATA, name)) as f:
        model = json.load(f)
    if kind == "dense":
        model = {**model, "num_hidden_layers": 3, "torch_dtype": "float32", "family": "llm"}
    return model


def prompt_of(model, n, salt):
    rng = np.random.default_rng(1000 * salt + n)
    return [int(t) for t in rng.integers(0, model["vocab_size"], n)]


def leaves_of(job):
    return jax.tree.leaves(job["cache"])


@pytest.fixture(scope="module")
def served():
    """(kind, chunk) -> what an engine of that kind served over ROUNDS,
    built on first use and kept: the engine, its registry, every
    ``_start_prefill`` call as (bucket, batch), the counters' state after
    the rounds and the requests with their tokens."""
    made = {}

    def get(kind, chunk):
        if (kind, chunk) not in made:
            made[kind, chunk] = serve_rounds(kind, chunk)
        return made[kind, chunk]

    return get


def serve_rounds(kind, chunk):
    model = model_of(kind)
    family = families.load(model["family"])
    cfg, paged = family.build(model, GEOMETRY)
    params = jax.jit(lambda words: family.params_tree(model, words))(weights.seed_words(SEED))
    registry = MetricsRegistry()
    eng = ServingEngine(
        cfg, params, paged, max_slots=4, prefill_chunk=chunk, decode_block=4,
        admission="optimistic", metrics=EngineMetrics(registry),
    )
    groups, start = [], eng._start_prefill

    def recorded(items):
        start(items)
        job = eng._pending[-1]
        groups.append((job["bucket"], job["batch"]))

    eng._start_prefill = recorded
    cases = []
    for salt, lengths in enumerate(ROUNDS):
        prompts = [prompt_of(model, n, salt) for n in lengths]
        done = eng.run([(p, NEW) for p in prompts])
        cases += [{"prompt": p, "tokens": list(r.tokens)} for p, r in zip(prompts, done)]
    eng._start_prefill = start
    return {
        "eng": eng, "registry": registry, "groups": groups, "state": eng.prefill_cache_state(),
        "cases": cases, "model": model, "family": family,
    }


KINDS = pytest.mark.parametrize("kind,chunk", [("dense", 8), ("dense", None), ("mixer", 8), ("mixer", None)])


def metric(registry, series):
    [line] = [l for l in registry.render().splitlines() if l.startswith(series + " ")]
    return float(line.split()[-1])


@KINDS
def test_one_dispatch_a_group_and_one_maker_a_shape(served, kind, chunk):
    """The counters against the admissions recorded: a dispatch a job,
    and a compiled maker for every (bucket, batch) met, not for every
    prompt, prompt length or job."""
    got = served(kind, chunk)
    eng, groups = got["eng"], got["groups"]
    assert sorted(groups) == sorted(GROUPS)
    assert got["state"] == {"jobs": len(groups), "dispatches": len(groups), "programs": len(set(groups))}
    assert set(eng._prefill_cache_makers) == set(groups)
    assert len(got["cases"]) == sum(len(r) for r in ROUNDS) > len(groups)
    # /metrics says what the engine counts.
    assert metric(got["registry"], "tpu_engine_prefill_jobs_total") == eng.prefill_jobs
    assert metric(got["registry"], "tpu_engine_prefill_cache_dispatches_total") == eng.prefill_cache_dispatches
    assert eng.prefill_jobs == eng.prefill_cache_dispatches >= len(groups)


@KINDS
def test_served_tokens_are_the_references_first(served, kind, chunk):
    """Every request of every round against the family's plain reference
    (float32 on both sides: rounding alone)."""
    got = served(kind, chunk)
    cases = got["cases"]
    assert all(len(c["tokens"]) == NEW for c in cases)
    rows = got["family"].served_gaps(got["model"], SEED, cases, pad_to=32, control=False)
    assert max(g for row in rows for g in row["gaps"]) < 1e-3


@KINDS
def test_the_makers_placement_recompiles_no_chunk_program(served, kind, chunk):
    """(8, 2) and (16, 1) were admitted three times each: were a maker's
    leaves placed or committed otherwise from call to call (or otherwise
    than the chunk program's own output, which the later chunks of a job
    pass back in), the donating chunk program would hold several
    executables for its key."""
    eng = served(kind, chunk)["eng"]
    assert {(bucket, batch) for (_, batch, bucket) in eng._prefill_cache} == set(GROUPS)
    assert all(fn._cache_size() == 1 for fn in eng._prefill_cache.values())
    assert all(fn._cache_size() == 1 for fn in eng._prefill_cache_makers.values())


@pytest.mark.parametrize("kind", ["dense", "mixer"])
def test_two_pending_jobs_of_one_key_hold_disjoint_buffers(served, kind):
    """A prompt of three chunks is mid-stream when another of its bucket
    is admitted: two jobs of the key (32, 1) pending at once.  The second's
    cache is a fresh dispatch of the same maker: none of its leaves is a
    buffer the first job holds (or donated), and no two leaves of one
    tree share a buffer, since the chunk program donates the whole tree."""
    got = served(kind, 8)  # an unchunked job completes in the step that admits it
    eng, model = got["eng"], got["model"]
    first, second = prompt_of(model, 21, 90), prompt_of(model, 19, 91)
    alone = [list(r.tokens) for r in (eng.run([(first, NEW)])[0], eng.run([(second, NEW)])[0])]
    before = eng.prefill_cache_state()
    a = eng.submit(first, NEW)
    eng.step()
    b = eng.submit(second, NEW)
    eng.step()
    assert [(j["bucket"], j["batch"]) for j in eng._pending] == [(32, 1), (32, 1)]
    held = [leaf.unsafe_buffer_pointer() for job in eng._pending for leaf in leaves_of(job)]
    assert len(set(held)) == len(held) == 2 * len(leaves_of(eng._pending[0]))
    assert not any(leaf.is_deleted() for job in eng._pending for leaf in leaves_of(job))
    while not (a.done and b.done):
        eng.step()
    assert [list(a.tokens), list(b.tokens)] == alone
    assert eng.prefill_cache_state() == {
        "jobs": before["jobs"] + 2, "dispatches": before["dispatches"] + 2, "programs": before["programs"],
    }
