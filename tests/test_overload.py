"""Overload control (models/engine_overload.py + the admission hooks in
engine_admission.py): priority ordering, per-tenant fairness, deadline
expiry/infeasibility sheds, the AIMD limiter's step response, submit-side
shedding, and the bit-identical-with-controller-off contract.

Budget note: compiles are this suite's cost, so the engine
tests ride the session-scoped compiled ``shared_engine`` fixture
(tests/conftest.py) and are shaped so admission never needs a prefill
program earlier suites haven't compiled: prompts stay in the warmed
length buckets and at most ONE slot frees at a time (a long-running
occupant pins the other), so every admission group is batch-1 — zero
new XLA compiles.  The limiter/selection/shed-policy units drive the
controller directly with a fake clock and bare Request records (no
engine, no jax arrays)."""

import time

import pytest

from k8s_device_plugin_tpu.models.engine_overload import (
    PRIORITY_HIGH,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    SHED_EXPIRED,
    SHED_INFEASIBLE,
    SHED_OVERLOAD,
    SHED_QUEUE_FULL,
    OverloadConfig,
    OverloadController,
    ShedError,
    parse_priority,
)
from k8s_device_plugin_tpu.models.engine_types import Request


def _req(prompt_len=3, max_new=4, **kw):
    return Request([1] * prompt_len, max_new, **kw)


def _ctl(max_slots=8, clock=None, **cfg_kw):
    cfg = OverloadConfig(**cfg_kw) if cfg_kw else None
    if clock is None:
        return OverloadController(max_slots, cfg)
    return OverloadController(max_slots, cfg, now=lambda: clock[0])


# ======================================================================
# Controller units (no engine)
# ======================================================================


def test_parse_priority_names_and_ints():
    assert parse_priority("high") == PRIORITY_HIGH
    assert parse_priority("Normal") == PRIORITY_NORMAL
    assert parse_priority("low") == PRIORITY_LOW
    assert parse_priority(0) == 0 and parse_priority("2") == 2
    for bad in ("urgent", 3, -1, "1.5"):
        with pytest.raises(ValueError):
            parse_priority(bad)


def test_select_index_is_fifo_for_uniform_traffic():
    """Default-priority, single-tenant, deadline-free traffic must pick
    index 0 every time — the property that makes controller-on streams
    bit-identical to the FIFO engine."""
    ctl = _ctl()
    queue = [_req() for _ in range(5)]
    assert ctl.select_index(queue) == 0
    # Even after admissions charged debt (one tenant: ties everywhere).
    ctl.observe_admission(queue[0], 0.01)
    assert ctl.select_index(queue[1:]) == 0


def test_select_index_priority_then_deadline():
    ctl = _ctl()
    queue = [
        _req(priority=PRIORITY_LOW),
        _req(priority=PRIORITY_NORMAL),
        _req(priority=PRIORITY_HIGH, deadline=100.0),
        _req(priority=PRIORITY_HIGH, deadline=50.0),
    ]
    # Best class first; earliest deadline inside it.
    assert ctl.select_index(queue) == 3
    queue.pop(3)
    assert ctl.select_index(queue) == 2
    # Cancelled entries are invisible to selection.
    queue[2].cancelled = True
    assert ctl.select_index(queue) == 1


def test_select_index_tenant_fairness_by_token_cost():
    """Token-cost debt, not request count: after one HEAVY admission the
    light tenant goes first, and weights scale the share."""
    ctl = _ctl()
    heavy = _req(prompt_len=64, max_new=64, tenant="heavy")
    ctl.observe_admission(heavy, 0.01)  # heavy owes 128 tokens of debt
    queue = [
        _req(tenant="heavy"),
        _req(tenant="light"),
    ]
    assert ctl.select_index(queue) == 1
    # Weighted: with both tenants in debt, a big weight divides heavy's
    # share below light's and buys the next slot back.
    ctl2 = _ctl(tenant_weights={"heavy": 1e6})
    ctl2.observe_admission(
        _req(prompt_len=64, max_new=64, tenant="heavy"), 0.01
    )
    ctl2.observe_admission(_req(tenant="light"), 0.01)  # light owes 7
    assert ctl2.select_index(queue) == 0


def test_aimd_limiter_step_response():
    """Multiplicative decrease while measured wait is over target,
    additive recovery while under, clamped to [min_concurrency,
    max_slots] — driven on a fake clock."""
    clock = [0.0]
    ctl = _ctl(
        max_slots=8,
        clock=clock,
        target_queue_wait_s=0.5,
        adjust_interval_s=1.0,
        aimd_increase=1.0,
        aimd_decrease=0.5,
    )
    assert ctl.concurrency_limit() == 8
    limits = []
    for _ in range(5):
        ctl.observe_admission(_req(), 2.0)  # way over target
        clock[0] += 1.1
        ctl.maybe_adjust()
        limits.append(ctl.concurrency_limit())
    assert limits == [4, 2, 1, 1, 1]  # halves, then floors
    assert ctl.limit_decreases >= 3
    for _ in range(12):
        ctl.observe_admission(_req(), 0.01)  # healthy again
    for _ in range(12):
        clock[0] += 1.1
        ctl.maybe_adjust()
    assert ctl.concurrency_limit() == 8  # additive recovery, capped
    assert ctl.limit_increases >= 7
    # Rate limit: two adjusts inside one interval collapse to one.
    before = ctl.limit
    ctl.maybe_adjust()
    assert ctl.limit == before


def test_check_admission_sheds_lowest_priority_first():
    clock = [0.0]
    ctl = _ctl(
        max_slots=4, clock=clock, target_queue_wait_s=0.5,
        shed_wait_factor=2.0, max_queue=100,
    )
    # No drain-rate estimate yet: never shed on a guess.
    ctl.check_admission(PRIORITY_LOW, 50)
    # Seed the drain rate at 1 req/s (two finishes 1s apart).
    done = _req()
    done.finished_at = 1.0
    ctl.on_finish(done)
    clock[0] = 1.0
    ctl.on_finish(done)
    # Projected wait at depth 3 = 3s; allowed: low 1s, normal 2s, high 4s.
    with pytest.raises(ShedError) as e:
        ctl.check_admission(PRIORITY_LOW, 3)
    assert e.value.kind == SHED_OVERLOAD
    assert e.value.retry_after_s >= 1.0
    with pytest.raises(ShedError):
        ctl.check_admission(PRIORITY_NORMAL, 3)
    ctl.check_admission(PRIORITY_HIGH, 3)  # high rides the deepest queue
    # The hard cap sheds any priority.
    with pytest.raises(ShedError) as e:
        ctl.check_admission(PRIORITY_HIGH, 100)
    assert e.value.kind == SHED_QUEUE_FULL


def test_expiry_and_infeasibility_predicates():
    clock = [10.0]
    ctl = _ctl(clock=clock)
    assert not ctl.expired(_req())  # no deadline, never expires
    assert ctl.expired(_req(deadline=9.0))
    assert not ctl.expired(_req(deadline=11.0))
    # Infeasible: remaining tokens cannot fit the remaining budget at
    # the measured per-token latency.
    req = _req(max_new=100, deadline=10.5)  # 0.5s left, 100 tokens to go
    assert not ctl.infeasible(req)  # no ITL estimate: no opinion
    ctl.observe_itl(0.1)  # 100 * 0.1s >> 0.5s
    assert ctl.infeasible(req)
    ctl._itl_ewma = 0.001  # 100 * 1ms = 0.1s < 0.5s: feasible again
    assert not ctl.infeasible(req)
    assert ctl.infeasible(_req(max_new=4, deadline=9.0))  # already past


def test_record_shed_accounting_and_snapshot():
    ctl = _ctl()
    req = _req(priority=PRIORITY_LOW, tenant="t1")
    req.rid = 7
    ctl.record_shed(req, SHED_EXPIRED, waited_s=0.5)
    ctl.record_shed(None, SHED_OVERLOAD, priority=PRIORITY_LOW, tenant="t1")
    snap = ctl.snapshot()
    assert snap["enabled"] is True
    assert snap["sheds_total"] == 2
    assert snap["sheds_by_kind"] == {SHED_EXPIRED: 1, SHED_OVERLOAD: 1}
    assert snap["tenants"]["t1"]["shed"] == 2


# ======================================================================
# Engine integration (session-scoped compiled engine; batch-1 admissions)
# ======================================================================

LONG = ([3, 141, 59], 25)  # pins one slot for a whole test (bucket 4)
SHORT = ([9, 10], 4)  # the other slot's occupant (bucket 2)


def _drain(eng, subs, guard=8000):
    while not all(r.done for r in subs):
        eng.step()
        guard -= 1
        assert guard > 0, "engine failed to drain"


@pytest.fixture
def overload_engine(shared_engine):
    """The shared engine with a controller attached for one test; always
    detached (and drained/pool-checked) on the way out so later suites
    see the stock FIFO engine."""
    _, _, eng = shared_engine
    yield eng
    eng.overload = None
    assert all(s is None for s in eng.slots) and not eng.queue
    assert len(eng.free_pages) == eng.paged.num_pages - 1


def _attach(eng, **cfg_kw):
    cfg_kw.setdefault("shed_wait_factor", 1e9)  # isolate the path under test
    ctl = OverloadController(
        eng.max_slots, OverloadConfig(**cfg_kw), flight=eng.flight
    )
    eng.overload = ctl
    return ctl


def test_priority_admission_order(overload_engine):
    """With one slot pinned by a long decode, queued work admits
    strictly by priority class regardless of arrival order."""
    eng = overload_engine
    _attach(eng)
    pinner = eng.submit(*LONG)
    occupant = eng.submit(*SHORT)
    eng.step()  # both in slots; queue empty
    lo = eng.submit([3, 141, 60], 3, priority="low")
    norm = eng.submit([3, 141, 61], 3, priority="normal")
    hi = eng.submit([3, 141, 62], 3, priority="high")
    _drain(eng, [pinner, occupant, lo, norm, hi])
    assert 0 < hi.admitted_at < norm.admitted_at < lo.admitted_at
    assert all(len(r.tokens) == 3 for r in (lo, norm, hi))


def test_tenant_fairness_interleaves_admissions(overload_engine):
    """Token-cost fair sharing: after tenant A's first (heavy)
    admission, tenant B's request jumps A's remaining backlog."""
    eng = overload_engine
    _attach(eng)
    pinner = eng.submit(*LONG)
    eng.step()
    a1 = eng.submit([3, 141, 63], 6, tenant="A")
    a2 = eng.submit([3, 141, 64], 3, tenant="A")
    b1 = eng.submit([3, 141, 65], 3, tenant="B")
    _drain(eng, [pinner, a1, a2, b1])
    # a1 first (FIFO among zero-debt tenants), then B before A again.
    assert 0 < a1.admitted_at < b1.admitted_at < a2.admitted_at


def test_expired_queued_request_sheds_without_pages(overload_engine):
    """A queued request whose deadline passes is swept: 'expired' shed,
    zero tokens, never admitted, never a page — and the decision is a
    flight event carrying the rid (what chaos scoring joins on)."""
    eng = overload_engine
    ctl = _attach(eng)
    shed0 = len(eng.flight.window(kinds=["admission.shed"]))
    pinner = eng.submit(*LONG)
    occupant = eng.submit([9, 10], 12)
    eng.step()
    doomed = eng.submit([3, 141, 66], 4, deadline_s=0.01, priority="low")
    time.sleep(0.03)
    fins = eng.step()
    assert doomed in fins and doomed.done
    assert doomed.shed == SHED_EXPIRED
    assert doomed.tokens == [] and doomed.admitted_at == 0.0
    events = eng.flight.window(kinds=["admission.shed"])[shed0:]
    assert any(
        e["shed"] == SHED_EXPIRED and e["rid"] == doomed.rid for e in events
    )
    assert ctl.shed_counts[SHED_EXPIRED] >= 1
    _drain(eng, [pinner, occupant])


def test_infeasible_slot_is_preempted_and_pages_return(overload_engine):
    """An IN-SLOT request whose deadline can no longer be met is shed
    mid-decode: slot torn down, pages back in the pool, partial tokens
    kept on the record."""
    eng = overload_engine
    _attach(eng)
    victim = eng.submit([3, 141, 67], 25, deadline_s=0.05)
    eng.step()  # admitted, decoding
    assert victim.admitted_at > 0
    time.sleep(0.08)  # deadline passes mid-decode
    _drain(eng, [victim])
    assert victim.shed == SHED_INFEASIBLE
    assert len(victim.tokens) < 25
    assert len(eng.free_pages) == eng.paged.num_pages - 1


def test_submit_side_queue_cap_sheds_with_retry_after(overload_engine):
    """The hard queue cap raises ShedError AT SUBMIT (the request never
    enqueues) with an honest retry-after, and records the decision."""
    eng = overload_engine
    ctl = _attach(eng, max_queue=1)
    pinner = eng.submit(*LONG)
    eng.step()  # admit before the next submit so the cap sees depth 0
    occupant = eng.submit(*SHORT)
    eng.step()
    queued = eng.submit([3, 141, 68], 3)  # depth 0 -> ok
    with pytest.raises(ShedError) as e:
        eng.submit([3, 141, 69], 3)  # depth 1 >= max_queue 1
    assert e.value.kind == SHED_QUEUE_FULL
    assert e.value.retry_after_s >= 1.0
    assert ctl.shed_counts[SHED_QUEUE_FULL] == 1
    assert len(eng.queue) == 1  # the shed request never enqueued
    _drain(eng, [pinner, occupant, queued])


def test_queue_that_idle_slots_absorb_is_not_projected_as_wait(overload_engine):
    """A drain rate learned from slow serial traffic (chip_smoke.py's
    cold warm-ups: one finish every ~20 s) must not shed a burst that
    free slots take at the very next step — only requests queued BEHIND
    what the slots can absorb wait at all (PR 21 bring-up: 2 of 8
    concurrent requests into 8 idle slots answered 503)."""
    eng = overload_engine
    ctl = _attach(eng, shed_wait_factor=8.0, target_queue_wait_s=0.5)
    pinner = eng.submit(*LONG)
    eng.step()  # one slot pinned, one idle
    ctl._drain_rate = 0.05
    first = eng.submit([3, 141, 70], 3)  # empty queue
    second = eng.submit([3, 141, 71], 3)  # one ahead, the idle slot takes it
    with pytest.raises(ShedError) as e:
        eng.submit([3, 141, 72], 3)  # one ahead that no slot can take: 20 s
    assert e.value.kind == SHED_OVERLOAD
    assert ctl.shed_counts[SHED_OVERLOAD] == 1
    _drain(eng, [pinner, first, second])


def test_aimd_limit_caps_admitted_concurrency(overload_engine):
    """With the limit forced to 1, a 2-slot engine leaves the second
    slot idle; restoring the limit fills it on the next step."""
    eng = overload_engine
    ctl = _attach(eng)
    ctl.limit = 1.0
    first = eng.submit(*LONG)
    second = eng.submit(*SHORT)
    eng.step()
    assert sum(1 for s in eng.slots if s is not None) == 1
    assert first.admitted_at > 0 and second.admitted_at == 0.0
    ctl.limit = 2.0
    eng.step()
    assert second.admitted_at > 0
    _drain(eng, [first, second])


def test_streams_bit_identical_controller_on_vs_off(shared_engine):
    """The whole point of default-off: greedy AND sampled token streams
    are bit-identical with the controller attached (uniform priorities,
    no deadlines — selection degenerates to FIFO) and without it."""
    import jax

    _, _, eng = shared_engine
    jobs = [([3, 141, 59], 8), ([9, 10], 6)]

    def _serve(sample):
        eng._rng = eng._rep(jax.random.PRNGKey(41))
        eng._mark_state_dirty()
        kw = {"temperature": 0.9, "top_k": 40} if sample else {}
        return [r.tokens for r in eng.run(jobs, **kw)]

    eng.overload = OverloadController(eng.max_slots, flight=eng.flight)
    on_greedy, on_sampled = _serve(False), _serve(True)
    eng.overload = None
    off_greedy, off_sampled = _serve(False), _serve(True)
    assert on_greedy == off_greedy
    assert on_sampled == off_sampled
    assert len(eng.free_pages) == eng.paged.num_pages - 1


def test_debug_state_overload_block(shared_engine):
    _, _, eng = shared_engine
    assert eng.debug_state()["overload"] == {"enabled": False}
    assert eng.overload_state() == {"enabled": False}
    eng.overload = OverloadController(eng.max_slots)
    try:
        block = eng.debug_state()["overload"]
        assert block["enabled"] is True
        assert block["limit"] == eng.max_slots
        assert "sheds_by_kind" in block and "tenants" in block
    finally:
        eng.overload = None
