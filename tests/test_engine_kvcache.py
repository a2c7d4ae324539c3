"""KV cache tiering (models/engine_kvcache.py).

Tier 1 retains dead-but-valid prefix pages (trie links live, reclaimed
LRU/leaf-first under pool pressure); tier 2 spills reclaimed pages and
preemption snapshots into a bounded host-RAM arena and restores them
device-side instead of recomputing.  The correctness oracle throughout
is the retention knob itself: flipping it must never change a token
stream, because a restored page carries exactly the bytes the graft (or
decode append) originally wrote — and recompute at the same length
bucket writes the same bytes.

Budget note: compiles are this suite's cost, so every test
reuses the session-scoped compiled engine (tests/conftest.py
``shared_engine``), keeps prompts inside the length buckets other tests
already compile (<= 4 tokens -> bucket 4), and samples with plain
temperature (no top-k/top-p, so the unfiltered step program is reused —
a filtered variant would be a fresh XLA compile).  The trie/teardown
invariant tests drive the host-side bookkeeping directly: zero device
work.  Each test restores the fixture to its default state (retention
off, tiers empty, pool whole) so later files see the engine they expect.
"""

from collections import Counter

import pytest


def _drain(eng, subs, guard=4000):
    while not all(r.done for r in subs):
        eng.step()
        guard -= 1
        assert guard > 0, "engine failed to drain"


@pytest.fixture()
def tiered_engine(shared_engine):
    """The shared engine with both tiers flipped on for one test, and
    restored to the fixture default (retention off, tiers empty, pool
    whole) afterwards — the same host-knob discipline the overlap suite
    uses for ``_overlap_steps``."""
    cfg, params, eng = shared_engine
    eng._kv_retain = True
    eng._kv_arena.budget_bytes = 8 << 20
    try:
        yield cfg, params, eng
    finally:
        eng._kv_retain = False
        eng.kvcache_clear()
        eng._kv_arena.budget_bytes = 0
        eng._optimistic = False
        assert len(eng.free_pages) == eng.paged.num_pages - 1


def test_repeated_prefix_equivalence_greedy_and_sampled(tiered_engine):
    """Bit-identical token streams with retention on vs off, greedy AND
    sampled, over a repeated-prefix workload whose lifetimes never
    overlap — live prefix sharing cannot help, so an on/off difference
    in pool traffic is attributable to the retained tier alone.  The
    warm run must actually hit the tier (revived pages observed)."""
    cfg, params, eng = tiered_engine
    prompt = [3, 141, 59, 7]  # one FULL page (page_size 4): registrable
    for kw in ({}, {"temperature": 1.0}):
        key0 = eng._rng
        eng.kvcache_clear()
        seed = eng.run([(prompt, 6)], **kw)[0].tokens
        assert len(eng._kv_retained) >= 1, "finish did not retain the page"
        hits0 = eng.kv_retained_hits
        eng._rng = key0  # same key schedule for every variant
        warm = eng.run([(prompt, 6)], **kw)[0].tokens
        assert eng.kv_retained_hits > hits0, "warm run never hit the tier"
        eng._kv_retain = False
        eng.kvcache_clear()
        eng._rng = key0
        ref = eng.run([(prompt, 6)], **kw)[0].tokens
        eng._kv_retain = True
        assert seed == ref, (kw, seed, ref)
        assert warm == ref, (kw, warm, ref)
    # Retention holds pages back from the pool only while it is on.
    eng.kvcache_clear()
    assert len(eng.free_pages) == eng.paged.num_pages - 1


def test_host_offload_restore_roundtrip(tiered_engine):
    """A trie walk that ends at an offloaded chain restores from the
    host arena: reclaiming the retained page (as pool pressure would)
    offloads its rows; the next same-prefix request gets a fresh page
    with the rows written back — same stream, host hit counted, restore
    metered in the flight ring — and the restored page re-enters the
    trie, so a third request revives it device-side."""
    cfg, params, eng = tiered_engine
    prompt = [3, 141, 59, 7]
    ref = eng.run([(prompt, 6)])[0].tokens
    assert len(eng._kv_retained) >= 1
    with eng._lock:
        freed = eng._kv_reclaim(len(eng._kv_retained))
    assert freed >= 1 and eng.kv_offloads >= 1
    assert len(eng._kv_arena) >= 1
    assert len(eng.free_pages) == eng.paged.num_pages - 1  # reclaim freed all
    host0, flight0 = eng.kv_host_hits, len(
        eng.flight.window(kinds=["kvcache.restore"])
    )
    warm = eng.run([(prompt, 6)])[0].tokens
    assert warm == ref
    assert eng.kv_host_hits > host0, "host tier never hit"
    assert len(eng.flight.window(kinds=["kvcache.restore"])) > flight0
    retained0 = eng.kv_retained_hits
    again = eng.run([(prompt, 6)])[0].tokens
    assert again == ref
    assert eng.kv_retained_hits > retained0, "restored page not re-linked"


def test_release_teardown_under_page_reallocation(shared_engine):
    """The retained-tier invariant the teardown guards: a freed id that
    is immediately reallocated and re-registered with different content
    must never be reachable through a stale trie link — neither via its
    own old key nor via a surviving child link.  Pure host bookkeeping
    (no device work): pages are taken from the pool and registered by
    hand, exactly what _admit does under the lock."""
    cfg, params, eng = shared_engine
    ps = eng.paged.page_size
    toks = list(range(1, 2 * ps + 1))  # two full chunks
    chunk1, chunk2 = tuple(toks[:ps]), tuple(toks[ps:])
    eng._kv_retain = True
    try:
        with eng._lock:
            p1 = eng.free_pages.popleft()
            p2 = eng.free_pages.popleft()
            eng._page_refs[p1] = 1
            eng._page_refs[p2] = 1
            eng._register_prefix(toks, [p1, p2], 2, None)
            assert eng._match_prefix(toks, 8, {}) == [p1, p2]
            # Finish: both release at refcount zero -> both retained.
            eng._release_page(p1)
            eng._release_page(p2)
            assert set(eng._kv_retained) == {p1, p2}
            # Leaf-first: the reclaim pick must be the CHILD, not the
            # parent, so the surviving chain stays walkable.
            assert eng._kv_pick_reclaim(frozenset()) == p2
            # Force the worst case anyway: reclaim the PARENT while the
            # child is still retained.  The child's key dies with it.
            eng._kv_reclaim_page(p1)
            assert eng._match_prefix(toks, 8, {}) == []
            assert (p1, chunk2) not in eng._prefix_pages
            assert not eng._page_keys.get(p2)
            # Reallocate p1's id for DIFFERENT content and re-register:
            # the old tokens must not match, the new ones must match
            # only the new registration — never walk into p2.
            other = [t + 100 for t in toks]
            q1 = eng.free_pages.pop()  # reclaim appended p1 at the right
            assert q1 == p1, "deque order changed; test premise broken"
            eng._page_refs[q1] = 1
            eng._register_prefix(other, [q1], 1, None)
            assert eng._match_prefix(toks, 8, {}) == []
            assert eng._match_prefix(other, 8, {}) == [q1]
            # Seed-behavior path too: with retention OFF the release
            # frees and tears down directly (no retained stop-over).
            eng._kv_retain = False
            eng._release_page(q1)
            assert eng._match_prefix(other, 8, {}) == []
            assert q1 in eng.free_pages
            # Drop the orphaned retained child back into the pool.
            eng._kv_retain = True
            eng._kv_reclaim_page(p2)
    finally:
        eng._kv_retain = False
        eng.kvcache_clear()
    assert len(eng.free_pages) == eng.paged.num_pages - 1
    assert not eng._prefix_pages and not eng._page_refs


def test_preempt_restore_resume_skips_prefill(tiered_engine, monkeypatch):
    """Preemption under optimistic admission resumes by RESTORE: the
    victim's slot is rebuilt from the tiers (retained pages + the
    snapshot tail) with zero prefill steps re-run, and its final stream
    equals the never-preempted greedy decode bit for bit.  Pool pressure
    is real — free pages are parked aside so growth actually starves —
    and every preemption/resume is visible in the counters and the
    flight ring."""
    cfg, params, eng = tiered_engine
    jobs = [([3, 141, 59], 6), ([9, 10], 6)]
    refs = [eng.run([job])[0].tokens for job in jobs]
    eng.kvcache_clear()
    eng._optimistic = True
    with eng._lock:
        parked = [
            eng.free_pages.pop() for _ in range(len(eng.free_pages) - 3)
        ]
    calls: list[int] = []
    orig = eng._start_prefill
    monkeypatch.setattr(
        eng,
        "_start_prefill",
        lambda items: (calls.extend(r.rid for _, r, _, _ in items), orig(items))[1],
    )
    pre0, res0 = eng.preemptions, eng.kv_resumes_restored
    subs = [eng.submit(p, n) for p, n in jobs]
    try:
        _drain(eng, subs)
    finally:
        eng._optimistic = False
        with eng._lock:
            eng.kvcache_clear()
            for page in parked:
                eng.free_pages.append(page)
    assert eng.preemptions > pre0, "pool pressure never preempted"
    assert eng.kv_resumes_restored > res0, "no resume restored"
    assert eng.kv_resumes_recompute == 0
    # Zero prefill steps re-run for restored pages: every request
    # prefilled exactly once (its first admission), resumes included.
    assert all(n == 1 for n in Counter(calls).values()), Counter(calls)
    for req, ref in zip(subs, refs):
        assert req.tokens == ref, (req.rid, req.tokens, ref)
    events = eng.flight.window(kinds=["engine.resume"])
    assert events and all(e["mode"] == "restored" for e in events)
    assert all(e["recomputed_tokens"] == 0 for e in events)
    assert all(e["restored_tokens"] > 0 for e in events)
    # The preempt events carry the snapshot marker the resume relies on.
    preempts = eng.flight.window(kinds=["engine.preempt"])
    assert preempts and all(e["snapshot"] for e in preempts[-len(events):])
    assert len(eng.free_pages) == eng.paged.num_pages - 1
