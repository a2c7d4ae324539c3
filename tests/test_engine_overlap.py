"""Overlapped asynchronous decode pipeline (models/engine.py).

With ``overlap_steps=1`` (the default) the step loop dispatches decode
round N+1 from the fed-forward device state BEFORE consuming round N's
readback, so per-token host work hides behind device compute.  The
equivalence oracle here is the knob itself: flipping it must never
change a greedy token stream, because the overlapped dispatch is the
SAME jitted program fed the same state, only issued earlier.  (Every
dense-oracle test in test_engine.py already runs WITH overlap on — this
module pins the mode equivalence and the discard machinery.)

Budget note: compiles are this suite's cost, so both tests
reuse the session-scoped compiled engine (tests/conftest.py
``shared_engine``) — no new XLA compiles; prompts stay in the length
buckets the fixture's first run compiles.
"""

import numpy as np


JOBS = [([3, 141, 59], 8), ([9, 10], 6)]  # one length bucket, burst of 2


def _drain(eng, subs, guard=4000):
    while not all(r.done for r in subs):
        eng.step()
        guard -= 1
        assert guard > 0, "engine failed to drain"


def _serve(eng, overlap, jobs=JOBS):
    eng._overlap_steps = overlap
    subs = [eng.submit(p, n) for p, n in jobs]
    _drain(eng, subs)
    return [r.tokens for r in subs]


def test_greedy_overlap_equals_sync(shared_engine):
    """Bit-identical greedy token streams with overlap_steps 1 vs 0, the
    pipeline actually engaging (hits observed, profiler ratio visible),
    and the pool whole after both runs."""
    cfg, params, eng = shared_engine
    hits0 = eng.overlap_hits
    overlapped = _serve(eng, 1)
    hits_after = eng.overlap_hits
    assert hits_after > hits0, "overlap never engaged"
    assert eng._inflight is None, "in-flight record leaked past the drain"
    sync = _serve(eng, 0)
    assert eng.overlap_hits == hits_after, "sync run must not hit"
    assert overlapped == sync, (overlapped, sync)
    assert all(len(t) == n for t, (_, n) in zip(overlapped, JOBS))
    assert len(eng.free_pages) == eng.paged.num_pages - 1
    # The overlap is visible where operators look: per-step hit counts in
    # the profiler window, and the new dispatch/readback phases sampled.
    prof = eng.profiler.snapshot()
    assert prof["overlap"]["window_hits"] > 0
    assert prof["phases"]["dispatch"]["window_steps"] > 0
    assert prof["phases"]["readback"]["window_steps"] > 0
    assert prof["phases"]["host_gap"]["window_steps"] > 0
    eng._overlap_steps = 1  # restore the default for later tests


def test_overlap_discards_on_cancel_and_admission_churn(shared_engine):
    """Mid-stream cancels and admissions invalidate the in-flight
    dispatch: each costs exactly one wasted lane (a discard counted in
    metrics and recorded in the flight ring), never a wrong or lost
    token — the survivor's stream equals its churn-free sync decode.
    The fixture engine runs racecheck=True, so every dispatch/consume
    handoff here also rides the OwnerGuard."""
    cfg, params, eng = shared_engine
    eng._overlap_steps = 1
    d0 = eng.overlap_discards
    f0 = len(eng.flight.window(kinds=["overlap.discard"]))
    survivor = eng.submit([3, 141, 59], 20)
    eng.step()
    eng.step()  # pipeline primed: one step in flight
    victim = eng.submit([9, 10], 12)  # admission while a step is in flight
    eng.step()
    eng.cancel(victim)  # cancel mid-flight
    late = eng.submit([9, 10], 6)  # admission again, mid-decode
    _drain(eng, [survivor, victim, late])
    assert victim.cancelled and victim.done
    assert eng.overlap_discards > d0, "churn never forced a discard"
    # Discards are forensics events: the flight ring carries them (and
    # therefore any incident record's attached window does too).
    events = eng.flight.window(kinds=["overlap.discard"])
    assert len(events) > f0
    assert all(e["T"] >= 1 and e["reason"] for e in events)
    assert len(eng.free_pages) == eng.paged.num_pages - 1
    # The churn-surrounded streams must equal their isolated sync decode
    # (same engine, same compiled program — greedy is deterministic).
    eng._overlap_steps = 0
    [ref_survivor] = eng.run([([3, 141, 59], 20)])
    [ref_late] = eng.run([([9, 10], 6)])
    assert survivor.tokens == ref_survivor.tokens
    assert late.tokens == ref_late.tokens
    assert np.all(np.asarray(eng._chain) == 0)  # idle engine, clean chain
    eng._overlap_steps = 1  # restore the default
