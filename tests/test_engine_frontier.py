"""The frontier's chain write (models/engine_paging.py): optimistic
admission records the pages a pass hands out on the host and publishes
them to the device chain in ONE dispatch of one compiled program when the
pass ends.

The oracle is host truth: after EVERY ``_ensure_frontier`` pass the device
chain holds exactly ``_slot_pages`` at ``_slot_page_base`` for every ready
slot and nothing for an empty one.  ``watched`` wraps the pass on a live
engine, so each case checks every pass of its run; the served tokens are
dense ``greedy_generate``'s, as everywhere in the engine tests.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.transformer import (
    GPTConfig,
    PagedConfig,
    TransformerLM,
    greedy_generate,
)
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

PS = 4
PAGED = PagedConfig(page_size=PS, num_pages=48, max_pages_per_seq=12)
JOBS = [([3, 141, 59, 265, 35], 22), ([9, 10], 30), ([7, 1, 88, 4, 16, 23, 42, 8], 17)]


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=PAGED.max_len)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    oracle = {}

    def want(prompt, n):
        key = (tuple(prompt), n)
        if key not in oracle:
            out = greedy_generate(cfg, params, jnp.asarray(prompt, jnp.int32)[None, :], n)
            oracle[key] = np.asarray(out)[0, len(prompt):].tolist()
        return oracle[key]

    return cfg, params, want


@pytest.fixture(scope="module")
def engines(model):
    """kind -> engine, built on first use and kept: ``block`` (blocks of
    4 over three slots, optimistic) and ``step`` (single steps over two)
    compile their decode programs once a module."""
    cfg, params, _ = model
    made = {}

    def get(kind):
        if kind not in made:
            kw = {"block": dict(max_slots=3, decode_block=4), "step": dict(max_slots=2)}[kind]
            registry = MetricsRegistry()
            made[kind] = ServingEngine(
                cfg, params, PAGED, admission="optimistic", metrics=EngineMetrics(registry), **kw
            )
            made[kind].test_registry = registry
        return made[kind]

    return get


def assert_chain_is_host_truth(eng):
    chain = np.asarray(eng._chain)
    want = np.zeros_like(chain)
    for s in range(eng.max_slots):
        if eng.slots[s] is not None and eng._slot_ready[s]:
            base, pages = eng._slot_page_base[s], eng._slot_pages[s]
            want[s, base : base + len(pages)] = pages
    ready = [s for s in range(eng.max_slots) if eng.slots[s] is not None and eng._slot_ready[s]]
    empty = [s for s in range(eng.max_slots) if eng.slots[s] is None]
    np.testing.assert_array_equal(chain[ready + empty], want[ready + empty])
    held = chain[chain > 0].tolist()
    assert len(held) == len(set(held)), "a page in two rows (the prompts here share none)"


class watched:
    """Every ``_ensure_frontier`` pass of the engine inside the block:
    host truth after it, at most one chain dispatch in it, exactly one
    where the pass grew a page that a ready slot still holds."""

    def __init__(self, eng):
        self.eng, self.passes, self.lookaheads = eng, [], set()

    def __enter__(self):
        eng, inner = self.eng, self.eng._ensure_frontier

        def ensure(active, lookahead):
            d0, p0 = eng.chain_write_dispatches, eng.chain_pages_written
            held0 = {s: len(eng._slot_pages[s]) for s in active}
            left = inner(active, lookahead)
            grew = sum(len(eng._slot_pages[s]) - held0[s] for s in left)
            dispatches, pages = eng.chain_write_dispatches - d0, eng.chain_pages_written - p0
            assert pages == grew and dispatches == (1 if grew else 0), (dispatches, pages, grew)
            assert_chain_is_host_truth(eng)
            self.passes.append((lookahead, dispatches, pages))
            self.lookaheads.add(lookahead)
            return left

        eng._ensure_frontier = ensure
        return self

    def __exit__(self, *exc):
        del self.eng._ensure_frontier

    @property
    def dispatches(self):
        return sum(d for _, d, _ in self.passes)


def drain(eng, subs, guard=4000):
    for _ in range(guard):
        if all(r.done for r in subs):
            return
        eng.step()
    raise AssertionError("engine failed to drain")


def until_ready(eng, n, guard=200):
    """Step until ``n`` slots decode (prefilled, grafted, first token out)."""
    for _ in range(guard):
        if sum(bool(r) for r in eng._slot_ready) == n:
            return [s for s in range(eng.max_slots) if eng._slot_ready[s]]
        eng.step()
    raise AssertionError("slots never became ready")


def park(eng, leave):
    """Take all but ``leave`` pages out of the free list (a small pool)."""
    with eng._lock:
        return [eng.free_pages.pop() for _ in range(len(eng.free_pages) - leave)]


def unpark(eng, parked):
    with eng._lock:
        eng.free_pages.extend(parked)


@pytest.mark.parametrize("kind,overlap,furthest", [
    ("step", 0, 0),   # the synchronous loop: the next write alone
    ("step", 1, 1),   # one step in flight: its write too
    ("block", 0, 3),  # blocks of T=4, none in flight: T-1
    ("block", 1, 7),  # an overlapped block: 2T-1
])
def test_every_pass_leaves_the_chain_at_host_truth(model, engines, kind, overlap, furthest):
    _, _, want = model
    eng = engines(kind)
    eng._overlap_steps = overlap
    jobs = JOBS if kind == "step" else JOBS + [([5, 6, 7], 9)]  # one more than the slots
    programs = eng.cache_write_programs()
    with watched(eng) as seen:
        done = eng.run(jobs)
    assert [r.tokens for r in done] == [want(p, n) for p, n in jobs]
    assert max(seen.lookaheads) == furthest, seen.lookaheads
    assert seen.dispatches > 0 and eng.preemptions == 0
    # One program, whatever the pages, slots and steps: only a shape
    # grafted for the first time may have added a writer beside it.
    assert eng._cache_writers[("chain",)]._cache_size() == 1
    grafts = sum(1 for key in eng._cache_writers if key[0] == "graft")
    assert eng.cache_write_programs() == grafts + 2 >= programs
    assert len(eng.free_pages) == PAGED.num_pages - 1
    assert not np.asarray(eng._chain).any()


def test_a_pass_that_crosses_several_pages_and_one_that_outgrows_the_operand(model, engines):
    """Two ready slots, one pass with a lookahead of three pages: every
    page of both slots in ONE dispatch.  Then a pass that grows more
    pages than the operand has rows: several dispatches of the SAME
    program."""
    _, _, want = model
    eng = engines("block")
    jobs = [(JOBS[0][0], 30), (JOBS[2][0], 30)]
    subs = [eng.submit(p, n) for p, n in jobs]
    a, b = until_ready(eng, 2)
    held = len(eng._slot_pages[a]) + len(eng._slot_pages[b])
    d0, p0 = eng.chain_write_dispatches, eng.chain_pages_written
    with watched(eng) as seen:
        assert eng._ensure_frontier([a, b], 3 * PS) == [a, b]
        assert seen.passes == [(3 * PS, 1, eng.chain_pages_written - p0)]
        grown = len(eng._slot_pages[a]) + len(eng._slot_pages[b]) - held
        assert grown == eng.chain_pages_written - p0 >= 5
        assert eng._ensure_frontier([a, b], 3 * PS) == [a, b]  # nothing to grow: no dispatch
        assert seen.passes[-1] == (3 * PS, 0, 0)
    rows = eng._chain_updates_len()
    assert rows == 3 * (2 + 1)
    before = len(eng._slot_pages[a]) + len(eng._slot_pages[b])
    inner = eng._chain_write
    calls = []
    eng._chain_write = lambda grown: (calls.append(len(grown)), inner(grown))[1]
    try:
        eng._ensure_frontier([a, b], 8 * PS)
    finally:
        del eng._chain_write
    [pages] = calls  # one call a pass ...
    assert pages == len(eng._slot_pages[a]) + len(eng._slot_pages[b]) - before > rows
    assert eng.chain_write_dispatches - d0 == 1 + -(-pages // rows)  # ... in as many dispatches as it takes
    assert eng._cache_writers[("chain",)]._cache_size() == 1
    assert_chain_is_host_truth(eng)
    with watched(eng):
        drain(eng, subs)
    assert [r.tokens for r in subs] == [want(p, n) for p, n in jobs]
    assert len(eng.free_pages) == PAGED.num_pages - 1


def test_a_starved_slot_that_evicts_itself_leaves_an_empty_row(model, engines):
    """The trap: the newest slot needs two pages and the pool holds one.
    It takes that one (recorded for the pass's write), finds nobody newer
    to rob, and evicts itself: its row is zeroed on the device at once
    and its pages go back to the pool.  The recorded update must not land
    after that zero."""
    _, _, want = model
    eng = engines("block")
    jobs = [(JOBS[0][0], 25), (JOBS[2][0], 25)]
    subs = [eng.submit(p, n) for p, n in jobs]
    ready = until_ready(eng, 2)
    old, new = sorted(ready, key=lambda s: eng._slot_seq[s])
    eng._ensure_frontier([old], 4 * PS)  # the older slot has room for the pass below
    parked = park(eng, leave=1)
    d0, p0, pre0 = eng.chain_write_dispatches, eng.chain_pages_written, eng.preemptions
    try:
        with watched(eng) as seen:
            assert eng._ensure_frontier([old, new], 4 * PS) == [old]
        assert seen.passes == [(4 * PS, 0, 0)]  # the one page handed out was dropped with its slot
    finally:
        unpark(eng, parked)
    assert eng.preemptions == pre0 + 1 and eng.slots[new] is None
    assert (eng.chain_write_dispatches, eng.chain_pages_written) == (d0, p0)
    assert not np.asarray(eng._chain)[new].any()
    with watched(eng):
        drain(eng, subs)
    assert [r.tokens for r in subs] == [want(p, n) for p, n in jobs]
    assert len(eng.free_pages) == PAGED.num_pages - 1


def test_a_dry_pool_preempts_the_newest_and_its_pages_move_once(model, engines):
    """The oldest slot grows into an empty pool: the newest ready slot is
    preempted, its row zeroed, its freed pages handed to the grower in
    the same pass.  Afterwards no page is in two rows and the victim's
    row is empty; the victim resumes and every stream is undisturbed."""
    _, _, want = model
    eng = engines("block")
    subs = [eng.submit(p, n) for p, n in JOBS]
    ready = until_ready(eng, 3)
    old, mid, new = sorted(ready, key=lambda s: eng._slot_seq[s])
    robbed = set(eng._slot_pages[new])
    ahead = 4 * PS
    need = {s: (eng._slot_len[s] + ahead) // PS + 1 - len(eng._slot_pages[s]) for s in (old, mid)}
    assert min(need.values()) >= 2 <= len(robbed)
    parked = park(eng, leave=sum(need.values()) - 1)  # the last page has to come from the newest slot
    pre0 = eng.preemptions
    try:
        with watched(eng) as seen:
            left = eng._ensure_frontier([old, mid, new], ahead)
        assert left == [old, mid] and eng.preemptions == pre0 + 1
        assert seen.passes == [(ahead, 1, sum(need.values()))]
    finally:
        unpark(eng, parked)
    assert not np.asarray(eng._chain)[new].any()
    assert robbed & set(eng._slot_pages[mid])
    with watched(eng):
        drain(eng, subs)
    assert [r.tokens for r in subs] == [want(p, n) for p, n in JOBS]
    assert len(eng.free_pages) == PAGED.num_pages - 1


def test_a_small_pool_churns_through_preemptions_with_every_pass_checked(model, engines):
    """The whole loop on a pool that cannot hold three streams: growth
    preempts and resumes again and again, and every pass of the run
    leaves the chain at host truth."""
    _, _, want = model
    eng = engines("block")
    parked = park(eng, leave=14)
    pre0 = eng.preemptions
    try:
        with watched(eng) as seen:
            done = eng.run(JOBS)
    finally:
        unpark(eng, parked)
    assert eng.preemptions > pre0 and seen.dispatches > 0
    assert [r.tokens for r in done] == [want(p, n) for p, n in JOBS]
    assert eng._cache_writers[("chain",)]._cache_size() == 1
    assert len(eng.free_pages) == PAGED.num_pages - 1


def test_reserve_admission_dispatches_no_chain_write(model, engines):
    """Reserve admission allocates at admission: a pass publishes nothing
    and the chain writer is never called."""
    _, _, want = model
    eng = engines("block")
    eng._optimistic = False
    d0 = eng.chain_write_dispatches
    try:
        with watched(eng) as seen:
            done = eng.run(JOBS[:2])
    finally:
        eng._optimistic = True
    assert seen.passes and seen.dispatches == 0 and eng.chain_write_dispatches == d0
    assert [r.tokens for r in done] == [want(p, n) for p, n in JOBS[:2]]


def test_counters_and_the_profile_block(model, engines):
    """The two counters on /metrics and the ``chain`` entry of
    ``cache_writes`` say what the engine counted; the op-labelled
    dispatch counter holds graft and slot alone."""
    _, _, want = model
    eng = engines("block")
    [done] = eng.run(JOBS[:1])
    assert done.tokens == want(*JOBS[0])
    text = eng.test_registry.render()
    state = eng.cache_writes_state()
    assert state["chain"] == {"dispatches": eng.chain_write_dispatches, "pages": eng.chain_pages_written}
    assert state["chain"]["pages"] >= state["chain"]["dispatches"] > 0
    assert set(state["dispatches"]) == {"graft", "slot"}
    assert state["programs"] == eng.cache_write_programs()
    assert f"tpu_engine_chain_write_dispatches_total {eng.chain_write_dispatches}" in text
    assert f"tpu_engine_chain_pages_written_total {eng.chain_pages_written}" in text
    assert f"tpu_engine_cache_write_programs {state['programs']}" in text
    assert 'op="chain"' not in text


def test_a_model_with_per_slot_state_runs_the_same_chain_write():
    """A mixer's ``slot_*`` leaves ride the graft and the slot writer; the
    chain writer takes the chain alone, so the state is no operand of it,
    and the served tokens are the plain reference's."""
    from chipbench import families, weights
    from chipbench.reference import falcon_h1 as ref

    with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-falcon-h1.json")) as f:
        conf = json.load(f)
    family = families.load("falcon_h1")
    cfg, paged = family.build(conf, {"page_size": 4, "num_pages": 64, "max_pages_per_seq": 16})
    params = jax.jit(lambda words: family.params_tree(conf, words))(weights.seed_words(11))
    rng = np.random.default_rng(5)
    jobs = [([int(t) for t in rng.integers(0, conf["vocab_size"], n)], new) for n, new in ((9, 14), (13, 11), (5, 18))]
    eng = ServingEngine(cfg, params, paged, max_slots=4, prefill_chunk=8, decode_block=4, admission="optimistic")
    assert eng.slot_state_bytes > 0
    with watched(eng) as seen:
        done = eng.run(jobs)
    assert seen.dispatches > 0 and 3 in seen.lookaheads
    assert eng.cache_write_dispatches == {"graft": 3, "slot": 3}
    assert eng._cache_writers[("chain",)]._cache_size() == 1
    cases = [{"prompt": p, "tokens": list(r.tokens)} for (p, _), r in zip(jobs, done)]
    rows = ref.served_gaps(conf, 11, cases, pad_to=48, control=False)
    assert max(g for row in rows for g in row["gaps"]) < 1e-3


def test_the_chain_write_keeps_the_chain_replicated_on_a_mesh(model):
    """On a ``tp`` mesh the writer's output is pinned to the chain's
    sharding and the host operand placed by ``_rep``: the sharding lint
    still passes and the bytes are the unsharded engine's.  No model
    program is built."""
    cfg, params, _ = model
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharded = ServingEngine(cfg, params, PAGED, max_slots=2, admission="optimistic", mesh=mesh)
    plain = ServingEngine(cfg, params, PAGED, max_slots=2, admission="optimistic")
    checked = sharded.assert_sharded()
    for eng in (sharded, plain):
        eng._chain_write([(1, 3, 9), (0, 0, 5), (1, 4, 2)])
        eng._chain_write([(0, 1, 7)])
    assert sharded.assert_sharded() == checked
    assert sharded._chain.sharding == sharded._rep_sharding
    want = np.zeros((2, PAGED.max_pages_per_seq), np.int32)
    want[1, 3], want[0, 0], want[1, 4], want[0, 1] = 9, 5, 2, 7
    for eng in (sharded, plain):
        np.testing.assert_array_equal(np.asarray(eng._chain), want)
        assert (eng.chain_write_dispatches, eng.chain_pages_written) == (2, 4)
        assert eng._cache_writers[("chain",)]._cache_size() == 1
