"""Prefix-affinity router (k8s_device_plugin_tpu/router/): tier-1 suite.

Everything here runs against FakeReplica doubles (tests/fakes.py) —
deterministic token streams, real sockets, zero JIT compiles, no jax
import — so the whole fault-handling surface (ring placement, breaker
state machine, retry budget, drain contract, hedging, mid-stream
failover) gets exercised in seconds inside the plugin tier.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time
import urllib.error
import urllib.request

import pytest

from k8s_device_plugin_tpu.router.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    RetryBudget,
)
from k8s_device_plugin_tpu.router.policy import (
    HOME,
    OVERFLOW,
    ReplicaState,
    RoutingPolicy,
)
from k8s_device_plugin_tpu.router.ring import HashRing, prefix_key
from k8s_device_plugin_tpu.router.server import RouterServer
from k8s_device_plugin_tpu.utils import failpoints
from k8s_device_plugin_tpu.utils.flight import FlightRecorder

from tests.fakes import FakeReplica, fake_generate

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_metrics_lint():
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(REPO_ROOT, "tools", "metrics_lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def wait_until(predicate, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


# ======================================================================
# Ring + prefix keys (pure)
# ======================================================================


def test_prefix_key_shared_prefix_collapses():
    """Prompts sharing their leading blocks key identically regardless
    of tails or trailing partial blocks — the property that routes one
    session's requests to one replica's warm KV."""
    prefix = list(range(100, 164))  # 4 x 16-token blocks
    k1 = prefix_key(prefix + [1, 2, 3])
    k2 = prefix_key(prefix + [9, 9, 9, 9, 9])
    k3 = prefix_key(prefix)
    assert k1 == k2 == k3
    # A different prefix keys elsewhere; a short prompt still keys.
    assert prefix_key([7] * 64) != k1
    assert isinstance(prefix_key([3]), int)
    # Only the first max_blocks blocks count.
    assert prefix_key(prefix + list(range(64))) == k1


def test_prefix_key_partial_block_rounds_down():
    """>= one block: trailing partial blocks are dropped (a 35-token
    prompt keys on its first 32 tokens), so near-identical prompts
    differing past the block boundary stay co-located."""
    base = list(range(32))
    assert prefix_key(base + [1, 2, 3], block_tokens=16) == prefix_key(
        base, block_tokens=16
    )
    # Below one block the whole prompt is the key.
    assert prefix_key([1, 2], block_tokens=16) != prefix_key(
        [1, 3], block_tokens=16
    )


def test_ring_deterministic_and_minimal_remapping():
    nodes = [f"10.0.0.{i}:8000" for i in range(4)]
    r1 = HashRing(nodes, vnodes=64)
    r2 = HashRing(list(reversed(nodes)), vnodes=64)
    keys = [prefix_key([i, i * 3, i + 7]) for i in range(2000)]
    # Construction order is irrelevant: same members, same placements.
    assert [r1.lookup(k) for k in keys] == [r2.lookup(k) for k in keys]
    # Adding a node remaps ~1/5 of keys, every one of them TO the new
    # node; removing it restores the original placement exactly.
    grown = HashRing(nodes + ["10.0.0.9:8000"], vnodes=64)
    moved = [k for k in keys if grown.lookup(k) != r1.lookup(k)]
    assert 0.10 < len(moved) / len(keys) < 0.35
    assert all(grown.lookup(k) == "10.0.0.9:8000" for k in moved)
    grown.remove("10.0.0.9:8000")
    assert [grown.lookup(k) for k in keys] == [r1.lookup(k) for k in keys]


def test_ring_order_is_distinct_failover_sequence():
    nodes = [f"n{i}:1" for i in range(5)]
    ring = HashRing(nodes, vnodes=32)
    key = prefix_key([42] * 32)
    order = ring.order(key)
    assert sorted(order) == sorted(nodes)  # every node, exactly once
    assert order[0] == ring.lookup(key)
    assert ring.order(key, limit=2) == order[:2]
    # Stable across instances (routers must agree without shared state).
    assert HashRing(nodes, vnodes=32).order(key) == order


# ======================================================================
# Breaker + retry budget (pure, injected clocks)
# ======================================================================


def test_breaker_state_machine_trip_probe_close():
    clock = [0.0]
    transitions = []
    cb = CircuitBreaker(
        failure_threshold=3,
        open_s=10.0,
        clock=lambda: clock[0],
        on_transition=lambda old, new: transitions.append((old, new)),
    )
    assert cb.state == CLOSED and cb.try_acquire()
    cb.record_failure()
    cb.record_failure()
    assert cb.state == CLOSED  # below threshold
    cb.record_failure()
    assert cb.state == OPEN
    assert not cb.try_acquire()  # cooldown running
    clock[0] = 10.1
    assert cb.try_acquire()  # the half-open probe
    assert cb.state == HALF_OPEN
    assert not cb.try_acquire()  # ONE probe at a time
    cb.record_success()
    assert cb.state == CLOSED
    assert transitions == [
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
    ]


def test_breaker_failed_probe_reopens_with_fresh_cooldown():
    clock = [0.0]
    cb = CircuitBreaker(
        failure_threshold=1, open_s=5.0, clock=lambda: clock[0]
    )
    cb.record_failure()
    assert cb.state == OPEN
    clock[0] = 5.1
    assert cb.try_acquire()
    cb.record_failure()  # probe failed
    assert cb.state == OPEN
    clock[0] = 9.0  # old cooldown would have expired; the fresh one hasn't
    assert not cb.try_acquire()
    clock[0] = 10.2
    assert cb.try_acquire()


def test_breaker_success_resets_consecutive_failures():
    cb = CircuitBreaker(failure_threshold=2, open_s=1.0)
    cb.record_failure()
    cb.record_success()
    cb.record_failure()
    assert cb.state == CLOSED  # never two CONSECUTIVE failures


def test_retry_budget_exhaustion_and_refill():
    clock = [0.0]
    budget = RetryBudget(capacity=2, refill_per_s=1.0, clock=lambda: clock[0])
    assert budget.try_spend() and budget.try_spend()
    assert not budget.try_spend()  # dry: degrade, don't amplify
    assert budget.exhausted_total == 1
    clock[0] = 1.5
    assert budget.try_spend()  # refilled at 1 token/s
    assert not budget.try_spend()
    clock[0] = 100.0
    assert budget.available() == pytest.approx(2.0)  # capped at capacity


# ======================================================================
# Policy (pure, stub states)
# ======================================================================


def _policy(names, mode="affinity", overflow_depth=4):
    ring = HashRing(names, vnodes=32)
    states = {
        n: ReplicaState(n, CircuitBreaker(failure_threshold=3, open_s=5.0))
        for n in names
    }
    return RoutingPolicy(
        ring, states, overflow_depth=overflow_depth, mode=mode
    ), states


def test_policy_home_then_ring_failover_order():
    policy, _ = _policy(["a:1", "b:1", "c:1"])
    prompt = [5] * 32
    order, tag = policy.candidates(prompt)
    assert tag == HOME
    assert order == policy.ring.order(policy.key_of(prompt))


def test_policy_excludes_draining_demotes_unreachable():
    policy, states = _policy(["a:1", "b:1", "c:1"])
    prompt = [5] * 32
    home = policy.candidates(prompt)[0][0]
    states[home].draining = True
    order, _ = policy.candidates(prompt)
    assert home not in order  # draining: NO new assignments, ever
    states[home].draining = False
    states[home].reachable = False
    order, _ = policy.candidates(prompt)
    assert order[-1] == home  # stale-poll hedge: last resort, not gone


def test_policy_overflow_rotates_off_hot_shard():
    policy, states = _policy(["a:1", "b:1", "c:1"], overflow_depth=3)
    prompt = [5] * 32
    ring_order = policy.ring.order(policy.key_of(prompt))
    home = ring_order[0]
    states[home].queue_depth = 10  # every other replica idle
    order, tag = policy.candidates(prompt)
    assert tag == OVERFLOW
    assert order[0] != home
    # Below the gap the home keeps its traffic (affinity beats a small
    # imbalance — that is the point of the threshold).
    states[home].queue_depth = 2
    order, tag = policy.candidates(prompt)
    assert tag == HOME and order[0] == home


def test_policy_random_mode_spreads_over_eligible():
    policy, _ = _policy(["a:1", "b:1", "c:1"], mode="random")
    prompt = [5] * 32
    firsts = {policy.candidates(prompt)[0][0] for _ in range(64)}
    assert firsts == {"a:1", "b:1", "c:1"}  # uniform control, not sticky


# ======================================================================
# End-to-end against FakeReplicas
# ======================================================================


def _fleet(n, router_kwargs=None, **replica_kwargs):
    """n started FakeReplicas + a started RouterServer over them."""
    replicas = [FakeReplica(**replica_kwargs).start() for _ in range(n)]
    flight = FlightRecorder(capacity=2048, name="router-test")
    kwargs = dict(
        poll_interval_s=0.1,
        breaker_open_s=0.3,
        backoff_base_s=0.02,
        backoff_max_s=0.2,
        hedge=False,
        upstream_timeout_s=10.0,
        request_timeout_s=30.0,
    )
    kwargs.update(router_kwargs or {})
    router = RouterServer(
        [r.name for r in replicas],
        host="127.0.0.1",
        port=0,
        flight=flight,
        **kwargs,
    ).start()
    return replicas, router, flight


def _teardown(replicas, router):
    router.stop()
    for r in replicas:
        if not r.killed.is_set():
            r.stop()


def _post(port, payload, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _stream(port, payload, timeout=30):
    """(events, tokens) from one SSE request through the router."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = dict(payload, stream=True)
    conn.request(
        "POST", "/generate", json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    events = []
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data:"):
            continue
        event = json.loads(line[5:].strip())
        events.append(event)
        if event.get("done") or "error" in event:
            break
    conn.close()
    tokens = [e["token"] for e in events if "token" in e]
    return events, tokens


def _home_prompt(router, replica_name, base=0, length=32):
    """A prompt whose ring home is ``replica_name``."""
    for salt in range(base, base + 500):
        prompt = [salt + 2] * length
        if router.ring.order(router.policy.key_of(prompt))[0] == replica_name:
            return prompt
    raise AssertionError(f"no prompt homes on {replica_name}")


def test_unary_roundtrip_affinity_sticky_and_correct():
    replicas, router, _ = _fleet(3)
    try:
        prompt = [11, 12, 13, 14]
        expect = fake_generate(prompt, 6)
        counts_before = [r.generate_requests for r in replicas]
        for _ in range(5):
            got = _post(router.port, {"prompt": prompt, "max_new_tokens": 6})
            assert got["tokens"] == expect
        deltas = [
            r.generate_requests - b
            for r, b in zip(replicas, counts_before)
        ]
        # Affinity: every repeat landed on ONE replica.
        assert sorted(deltas) == [0, 0, 5], deltas
        assert router.metrics.placements.value(placement="home") == 5
        assert router.metrics.requests.value(outcome="ok") == 5
    finally:
        _teardown(replicas, router)


def test_stream_roundtrip_matches_oracle():
    replicas, router, _ = _fleet(2, token_delay_s=0.002)
    try:
        prompt = [3, 1, 4, 1, 5]
        events, tokens = _stream(
            router.port, {"prompt": prompt, "max_new_tokens": 8}
        )
        assert tokens == fake_generate(prompt, 8)
        done = events[-1]
        assert done["done"] and done["tokens"] == tokens
        # Global indexes are contiguous from 0.
        assert [e["index"] for e in events if "token" in e] == list(range(8))
    finally:
        _teardown(replicas, router)


def test_unary_failover_on_dead_replica_and_breaker_trip():
    replicas, router, flight = _fleet(
        # Slow poll: the breaker (not the poll loop) must be what cuts
        # the dead replica out of the dial path here.
        3, router_kwargs=dict(breaker_failures=2, poll_interval_s=5.0)
    )
    try:
        victim = replicas[0]
        prompt = _home_prompt(router, victim.name)
        victim.kill()
        expect = fake_generate(prompt, 4)
        for _ in range(3):
            got = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
            assert got["tokens"] == expect  # failed over, same answer
        # Two dial failures tripped the breaker; later requests skip the
        # dead replica without dialing it (state visible in snapshot).
        snap = router.snapshot()
        assert snap["replicas"][victim.name]["breaker"]["state"] == "open"
        kinds = {e["kind"] for e in flight.snapshot()["events"]}
        assert "router.dispatch_error" in kinds
        assert "router.breaker_open" in kinds
        assert router.metrics.retries.value() >= 1
    finally:
        _teardown(replicas, router)


def test_mid_stream_failover_zero_drop_bit_identical():
    """THE zero-drop contract: kill the replica serving a stream
    mid-decode; the client sees one uninterrupted, bit-identical token
    stream completed by the failover replica (prompt + emitted tokens
    resubmitted, remaining budget, deterministic continuation)."""
    replicas, router, flight = _fleet(
        2, token_delay_s=0.02, router_kwargs=dict(breaker_failures=1)
    )
    try:
        victim = replicas[0]
        survivor = replicas[1]
        prompt = _home_prompt(router, victim.name)
        n_new = 16
        import threading

        holder = [None]

        def client():
            holder[0] = _stream(
                router.port, {"prompt": prompt, "max_new_tokens": n_new}
            )

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert wait_until(lambda: victim.active_streams > 0)
        time.sleep(0.06)  # a few tokens into the decode
        victim.kill()
        t.join(timeout=20)
        assert holder[0] is not None, "client stream never resolved"
        events, tokens = holder[0]
        assert tokens == fake_generate(prompt, n_new)  # bit-identical
        assert events[-1]["done"] and events[-1]["tokens"] == tokens
        assert [e["index"] for e in events if "token" in e] == list(
            range(n_new)
        )
        assert router.metrics.failovers.value() == 1
        fo = [
            e
            for e in flight.snapshot()["events"]
            if e["kind"] == "router.failover"
        ]
        assert fo and fo[0]["replica"] == victim.name
        assert 0 < fo[0]["emitted"] < n_new  # genuinely MID-stream
        assert survivor.generate_requests >= 1
    finally:
        _teardown(replicas, router)


def test_drain_stops_new_assignments_keeps_streams():
    """The rollout contract: a draining replica takes no new requests
    the moment the router learns of it, while its in-flight proxied
    stream runs to completion."""
    replicas, router, flight = _fleet(2, token_delay_s=0.03)
    try:
        draining = replicas[0]
        other = replicas[1]
        prompt = _home_prompt(router, draining.name)
        import threading

        holder = [None]

        def client():
            holder[0] = _stream(
                router.port, {"prompt": prompt, "max_new_tokens": 20}
            )

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert wait_until(lambda: draining.active_streams > 0)
        draining.begin_drain()
        assert wait_until(
            lambda: router.replicas[draining.name].draining, timeout=3
        ), "poll never observed the drain"
        served_at_drain = draining.generate_requests
        # New requests (even ones homed on the draining replica) go
        # elsewhere — and still answer correctly.
        for salt in range(4):
            p2 = _home_prompt(router, draining.name, base=100 + salt * 7)
            got = _post(router.port, {"prompt": p2, "max_new_tokens": 3})
            assert got["tokens"] == fake_generate(p2, 3)
        assert draining.generate_requests == served_at_drain
        assert other.generate_requests >= 4
        # The in-flight stream survived the whole drain.
        t.join(timeout=20)
        events, tokens = holder[0]
        assert events[-1]["done"] and tokens == fake_generate(prompt, 20)
        kinds = [e["kind"] for e in flight.snapshot()["events"]]
        assert "router.drain_begin" in kinds
    finally:
        _teardown(replicas, router)


def test_retry_after_honored_when_fleet_drains():
    """With EVERY replica draining, the router's backoff floors at the
    replicas' Retry-After instead of hammering them — and the request
    succeeds once the drain lifts."""
    replicas, router, _ = _fleet(1, router_kwargs=dict(poll_interval_s=0.05))
    try:
        replica = replicas[0]
        replica.begin_drain(retry_after="0.4")
        import threading

        def undrain_later():
            time.sleep(0.15)
            replica.undrain()

        threading.Thread(target=undrain_later, daemon=True).start()
        t0 = time.monotonic()
        got = _post(
            router.port, {"prompt": [9, 9], "max_new_tokens": 3}, timeout=15
        )
        elapsed = time.monotonic() - t0
        assert got["tokens"] == fake_generate([9, 9], 3)
        assert elapsed >= 0.35, (
            f"backoff ignored Retry-After (elapsed {elapsed:.3f}s)"
        )
        assert replica.drain_rejects >= 1
    finally:
        _teardown(replicas, router)


def test_hedge_races_slow_home_and_cancels_loser():
    """Home replica stalls in prefill; the hedge fires at the rolling-
    p99 floor, the fast replica wins, and the client gets the (identical)
    answer at hedge latency instead of stall latency."""
    fast = FakeReplica().start()
    slow = FakeReplica(prefill_delay_s=1.5).start()
    flight = FlightRecorder(capacity=512, name="hedge-test")
    router = RouterServer(
        [fast.name, slow.name],
        host="127.0.0.1",
        port=0,
        flight=flight,
        poll_interval_s=0.1,
        hedge=True,
        hedge_min_s=0.1,
        backoff_base_s=0.02,
    ).start()
    try:
        prompt = _home_prompt(router, slow.name)
        t0 = time.monotonic()
        got = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        elapsed = time.monotonic() - t0
        assert got["tokens"] == fake_generate(prompt, 4)
        assert elapsed < 1.2, f"hedge never rescued the stall ({elapsed:.2f}s)"
        assert router.metrics.hedges.value(result="won") == 1
        kinds = {e["kind"] for e in flight.snapshot()["events"]}
        assert "router.hedge" in kinds and "router.hedge_won" in kinds
        assert router.metrics.placements.value(placement="failover") == 1
    finally:
        _teardown([fast, slow], router)


def test_replica_conn_failpoint_scoped_to_one_replica():
    """The chaos seam: arming router.replica_conn.<name> faults dials to
    ONE replica (requests fail over); the generic site faults all."""
    replicas, router, flight = _fleet(
        2, router_kwargs=dict(breaker_failures=5)
    )
    try:
        target = replicas[0]
        prompt = _home_prompt(router, target.name)
        failpoints.arm(
            f"router.replica_conn.{target.name}", "error", count=2
        )
        got = _post(router.port, {"prompt": prompt, "max_new_tokens": 3})
        assert got["tokens"] == fake_generate(prompt, 3)
        assert replicas[1].generate_requests >= 1  # failed over
        assert target.generate_requests == 0
        kinds = {e["kind"] for e in flight.snapshot()["events"]}
        assert "router.dispatch_error" in kinds
    finally:
        failpoints.disarm_all()
        _teardown(replicas, router)


def test_retry_budget_exhaustion_degrades_to_503():
    """Budget capacity 0.5 token, no refill: the first extra dispatch is
    refused — with the only replica dead, the client gets a clean 503
    (degrade) instead of an infinite retry loop (amplify)."""
    replica = FakeReplica().start()
    router = RouterServer(
        [replica.name],
        host="127.0.0.1",
        port=0,
        poll_interval_s=5.0,  # poll must not mark it down first
        retry_budget=0.5,
        retry_refill_per_s=0.0,
        breaker_failures=100,  # isolate the budget from the breaker
        backoff_base_s=0.01,
        backoff_max_s=0.02,
        hedge=False,
        request_timeout_s=5.0,
    ).start()
    try:
        replica.kill()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(router.port, {"prompt": [1, 2], "max_new_tokens": 2})
        assert e.value.code == 503
        assert router.budget.exhausted_total >= 1
    finally:
        _teardown([replica], router)


def test_router_validation_healthz_and_debug_snapshot():
    replicas, router, _ = _fleet(2)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(router.port, {"max_new_tokens": 3})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(router.port, {"prompt": [], "max_new_tokens": 3})
        assert e.value.code == 400
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/healthz", timeout=5
        ) as resp:
            health = json.loads(resp.read())
        assert health["status"] == "ok" and health["reachable"] == 2
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/router", timeout=5
        ) as resp:
            snap = json.loads(resp.read())
        assert snap["policy"]["mode"] == "affinity"
        assert set(snap["replicas"]) == {r.name for r in replicas}
        for st in snap["replicas"].values():
            assert st["breaker"]["state"] == "closed"
        assert snap["ring"]["points"] == 2 * 64
    finally:
        _teardown(replicas, router)


def test_debug_postmortem_off_by_default_and_admin_gated(tmp_path):
    """The fleet collector surface: 404 while --postmortem is off; when
    armed, GET /debug/postmortem serves the ledger and POST
    /debug/postmortem/capture is admin-gated (403 until
    --postmortem-admin) — same gating shape as the fence/drain admin
    endpoints."""

    def _capture_post(port):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/debug/postmortem/capture",
            data=json.dumps({"incident_id": "operator-drill"}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            return json.loads(resp.read())

    replicas, router, _ = _fleet(2)
    try:
        assert router.postmortem is None
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{router.port}/debug/postmortem",
                timeout=5,
            )
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            _capture_post(router.port)
        assert e.value.code == 404
    finally:
        _teardown(replicas, router)

    replicas, router, _ = _fleet(
        2,
        router_kwargs=dict(
            postmortem=True,
            postmortem_dir=str(tmp_path),
            postmortem_admin=False,
        ),
    )
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/postmortem", timeout=5
        ) as resp:
            snap = json.loads(resp.read())
        assert snap["enabled"] is True
        assert snap["directory"] == str(tmp_path)
        assert snap["captures"] == 0 and snap["bundles"] == []
        with pytest.raises(urllib.error.HTTPError) as e:
            _capture_post(router.port)
        assert e.value.code == 403
    finally:
        _teardown(replicas, router)

    replicas, router, _ = _fleet(
        2,
        router_kwargs=dict(
            postmortem=True,
            postmortem_dir=str(tmp_path),
            postmortem_admin=True,
        ),
    )
    try:
        body = _capture_post(router.port)
        assert body["captured"] is True
        assert os.path.isdir(body["bundle"])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/postmortem", timeout=5
        ) as resp:
            snap = json.loads(resp.read())
        assert snap["captures"] == 1
        assert snap["bundles"][0]["incident_id"] == "operator-drill"
        assert snap["bundles"][0]["trigger"] == "manual"
    finally:
        _teardown(replicas, router)


def test_poll_marks_replica_down_and_up():
    replicas, router, flight = _fleet(2)
    try:
        victim = replicas[0]
        port = victim.port
        victim.kill()
        assert wait_until(
            lambda: not router.replicas[victim.name].reachable, timeout=3
        )
        assert router.metrics.replica_up.value(replica=victim.name) == 0
        # "Replug": a fresh replica on the same address recovers it.
        revived = FakeReplica(port=port).start()
        replicas.append(revived)
        assert wait_until(
            lambda: router.replicas[victim.name].reachable, timeout=3
        )
        kinds = [e["kind"] for e in flight.snapshot()["events"]]
        assert "router.replica_down" in kinds
        assert "router.replica_up" in kinds
    finally:
        _teardown(replicas, router)


def test_metrics_lint_clean_on_live_router(tmp_path):
    """The same strict exposition lint the MetricsServer and
    EngineServer endpoints pass, against a router that has actually
    routed (every family populated the interesting way)."""
    metrics_lint = _load_metrics_lint()
    replicas, router, _ = _fleet(2)
    try:
        for i in range(3):
            _post(router.port, {"prompt": [i + 1, 2], "max_new_tokens": 2})
        _stream(router.port, {"prompt": [5, 6], "max_new_tokens": 3})
        errors = metrics_lint.lint_url(
            f"http://127.0.0.1:{router.port}/metrics"
        )
        assert errors == [], errors
    finally:
        _teardown(replicas, router)


def test_ring_membership_change_updates_routing():
    """add_replica/remove_replica (the DNS-refresh path): a removed
    replica stops receiving traffic; the survivors keep their keyspace
    (consistent hashing, not a reshuffle)."""
    replicas, router, _ = _fleet(3)
    try:
        keys = [prefix_key([i + 2] * 32) for i in range(300)]
        before = {k: router.ring.lookup(k) for k in keys}
        gone = replicas[2]
        router.remove_replica(gone.name)
        after = {k: router.ring.lookup(k) for k in keys}
        assert gone.name not in set(after.values())
        stayed = [k for k in keys if before[k] != gone.name]
        assert all(after[k] == before[k] for k in stayed)
        got = _post(router.port, {"prompt": [1, 2, 3], "max_new_tokens": 2})
        assert got["tokens"] == fake_generate([1, 2, 3], 2)
        assert gone.generate_requests == 0  # never dialed after removal
        snap = router.snapshot()
        assert set(snap["replicas"]) == {replicas[0].name, replicas[1].name}
    finally:
        _teardown(replicas, router)


# ======================================================================
# Overload contract (ISSUE 9): deadline propagation, fail-fast 504,
# shed-503 handling (back off without ejecting), budget gating.
# ======================================================================


def test_deadline_propagates_decremented_to_replica():
    """The client's X-Request-Deadline rides every upstream dial as the
    REMAINING budget — stamped at dial time, so the replica sees a
    value no larger than what the client sent."""
    replicas, router, _ = _fleet(2)
    try:
        got = _post(
            router.port,
            {"prompt": [4, 4], "max_new_tokens": 3, "deadline_s": 7.5},
        )
        assert got["tokens"] == fake_generate([4, 4], 3)
        seen = [
            d
            for r in replicas
            for d in r.seen_deadlines
            if d is not None
        ]
        assert len(seen) == 1
        assert 0.0 < float(seen[0]) <= 7.5
    finally:
        _teardown(replicas, router)


def test_expired_deadline_fails_fast_without_dialing():
    """A spent deadline answers 504 at the router's front door: no
    upstream dial, no retry token, outcome=deadline."""
    replicas, router, flight = _fleet(2)
    try:
        before = sum(r.generate_requests for r in replicas)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(
                router.port,
                {"prompt": [4, 4], "max_new_tokens": 3, "deadline_s": 0},
            )
        assert e.value.code == 504
        assert sum(r.generate_requests for r in replicas) == before
        assert router.metrics.requests.value(outcome="deadline") == 1
        assert any(
            ev["kind"] == "router.deadline_exceeded"
            for ev in flight.window()
        )
    finally:
        _teardown(replicas, router)


def test_shed_503_backs_off_without_ejecting_replica():
    """An engine overload shed (503 + Retry-After + X-Shed) floors the
    router's backoff — end to end, through real sockets — and does NOT
    mark the replica draining: overload is a busy replica, not a dying
    one."""
    import threading

    replicas, router, flight = _fleet(
        2, router_kwargs=dict(poll_interval_s=0.05)
    )
    try:
        for r in replicas:
            r.begin_shed(retry_after="0.4", kind="overload")

        def recover_later():
            time.sleep(0.15)
            for r in replicas:
                r.end_shed()

        threading.Thread(target=recover_later, daemon=True).start()
        t0 = time.monotonic()
        got = _post(
            router.port, {"prompt": [6, 6], "max_new_tokens": 3, "deadline_s": 20},
            timeout=15,
        )
        elapsed = time.monotonic() - t0
        assert got["tokens"] == fake_generate([6, 6], 3)
        assert elapsed >= 0.35, (
            f"backoff ignored shed Retry-After (elapsed {elapsed:.3f}s)"
        )
        assert sum(r.shed_rejects for r in replicas) >= 2
        # Sheds never read as drain: the fleet stayed in rotation.
        assert all(not st.draining for st in router.replicas.values())
        kinds = [ev["kind"] for ev in flight.window()]
        assert "router.replica_shed" in kinds
        assert "router.drain_begin" not in kinds
    finally:
        _teardown(replicas, router)


def test_stream_deadline_eventually_504s_and_shed_stream_retries():
    """Streaming: a fleet-wide shed with a TIGHT deadline exhausts the
    budget and the client sees a definite 5xx verdict (no silent hang);
    with budget left, the stream retries past the shed and completes."""
    import http.client
    import threading

    replicas, router, _ = _fleet(1, router_kwargs=dict(poll_interval_s=0.05))
    try:
        replica = replicas[0]
        replica.begin_shed(retry_after="0.2")
        # Tight deadline: the shed + Retry-After floor outlive the
        # budget — a pre-stream 5xx, not a hang.
        conn = http.client.HTTPConnection("127.0.0.1", router.port, timeout=15)
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": [8, 8], "max_new_tokens": 3,
                        "stream": True, "deadline_s": 0.3}).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status in (503, 504), resp.status
        resp.read()
        conn.close()

        # Budget left: recovery mid-retry completes the stream whole.
        def recover_later():
            time.sleep(0.15)
            replica.end_shed()

        threading.Thread(target=recover_later, daemon=True).start()
        events, tokens = _stream(
            router.port,
            {"prompt": [8, 9], "max_new_tokens": 3, "deadline_s": 20},
            timeout=15,
        )
        assert tokens == fake_generate([8, 9], 3)
        assert events[-1].get("done") is True
    finally:
        _teardown(replicas, router)


# ======================================================================
# Replica self-fencing (summary `fenced` — ISSUE 10)
# ======================================================================


def test_policy_excludes_fenced_like_draining():
    """A fenced replica takes NO new assignments — not even as the
    stale-poll hedge an unreachable one gets (a fenced replica answers
    503 by contract; dialing it only burns a retry token)."""
    policy, states = _policy(["a:1", "b:1", "c:1"])
    prompt = [5] * 32
    home = policy.candidates(prompt)[0][0]
    states[home].fenced = True
    order, _ = policy.candidates(prompt)
    assert home not in order
    # Fenced beats unreachable-hedging too.
    states[home].reachable = False
    order, _ = policy.candidates(prompt)
    assert home not in order
    states[home].fenced = False
    states[home].reachable = True
    assert home in policy.candidates(prompt)[0]


def test_poll_marks_fenced_and_unfenced_with_flight_events():
    """The router's summary poll picks up ``fenced`` like ``draining``:
    router.replica_fenced flight event + per-replica gauge + no new
    assignments while fenced; the summary clearing promotes the replica
    back (router.replica_unfenced)."""
    replicas, router, flight = _fleet(2)
    try:
        a, b = replicas
        prompt = _home_prompt(router, a.name)
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        assert out["tokens"] == fake_generate(prompt, 4)
        assert a.generate_requests == 1 and b.generate_requests == 0

        a.begin_fence(reason="hung_step")
        assert wait_until(lambda: router.replicas[a.name].fenced)
        events = flight.window(kinds=["router.replica_fenced"])
        assert events and events[-1]["replica"] == a.name
        assert router.metrics.replica_fenced.value(replica=a.name) == 1
        # The fenced home gets NOTHING; its ring neighbor serves.
        for _ in range(3):
            out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
            assert out["tokens"] == fake_generate(prompt, 4)
        assert a.generate_requests == 1, "fenced replica was dialed"
        assert b.generate_requests == 3
        snap = router.snapshot()
        assert snap["replicas"][a.name]["fenced"] is True

        a.unfence()
        assert wait_until(lambda: not router.replicas[a.name].fenced)
        assert flight.window(kinds=["router.replica_unfenced"])
        assert router.metrics.replica_fenced.value(replica=a.name) == 0
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        assert a.generate_requests == 2, "unfenced home must serve again"
    finally:
        _teardown(replicas, router)


def test_fenced_503_dial_fails_over_before_poll_notices():
    """A fence landing BETWEEN polls: the dial's plain 503 (no X-Shed)
    must fail the request over to the next ring replica immediately —
    the client never sees the fence."""
    replicas, router, flight = _fleet(
        2, router_kwargs={"poll_interval_s": 30.0}  # poll will NOT save us
    )
    try:
        a, b = replicas
        prompt = _home_prompt(router, a.name)
        a.begin_fence(reason="chip_unplugged")
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        assert out["tokens"] == fake_generate(prompt, 4)
        assert a.fence_rejects == 1 and b.generate_requests == 1
    finally:
        _teardown(replicas, router)


def test_fenced_replica_in_flight_stream_finishes():
    """Fencing stops NEW assignments; a stream already running on the
    replica keeps flowing (the real server only cuts streams it cannot
    finish — the FakeReplica models the finishable case)."""
    replicas, router, flight = _fleet(2, token_delay_s=0.03)
    try:
        a, b = replicas
        prompt = _home_prompt(router, a.name)
        import threading

        result: dict = {}

        def _run():
            result["events"], result["tokens"] = _stream(
                router.port, {"prompt": prompt, "max_new_tokens": 12}
            )

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        assert wait_until(lambda: a.active_streams == 1)
        a.begin_fence()
        assert wait_until(lambda: router.replicas[a.name].fenced)
        t.join(timeout=10)
        assert result["tokens"] == fake_generate(prompt, 12)
        assert any(e.get("done") for e in result["events"])
    finally:
        _teardown(replicas, router)


def test_racecheck_owner_guard_on_poll_state():
    """RouterServer(racecheck=True) arms the poll-state OwnerGuard
    (utils/racecheck.py): the poll thread owns ReplicaState's
    poll-derived fields off-lock; any OTHER thread polling off-lock
    raises at the faulty call site, while the failover-path mutators
    (_mark_draining / _mark_fenced) stay legal from request threads
    because they take the router lock — and, with steal_on_lock=False,
    taking it does NOT steal ownership from the long-lived poll loop."""
    import threading

    from k8s_device_plugin_tpu.utils.racecheck import LockDisciplineError

    replicas, router, _ = _fleet(2, router_kwargs={"racecheck": True})
    try:
        victim = replicas[0].name
        # The poll thread has polled at least once (start() waits on the
        # first poll), so it owns the poll state.
        assert router._poll_guard._owner is router._poll_thread

        # A foreign thread (this one) polling OFF-LOCK is the exact
        # contract violation the guard exists for.
        with pytest.raises(LockDisciplineError):
            router._poll_once()

        # The stream-failover handoff from a request-shaped foreign
        # thread is LEGAL: _mark_draining/_mark_fenced take the router
        # lock (the cross-thread license)...
        errors: list = []

        def failover_path():
            try:
                router._mark_draining(victim, True)
                router._mark_fenced(victim, True)
                router._mark_fenced(victim, False)
                router._mark_draining(victim, False)
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        t = threading.Thread(target=failover_path, name="fake-request")
        t.start()
        t.join(timeout=5)
        assert not errors, errors

        # ...and did not steal ownership: the poll loop keeps polling
        # violation-free after the request thread's marks (a stolen
        # owner would false-trip the next poll tick).
        assert router._poll_guard._owner is router._poll_thread
        before = router.replicas[victim].last_poll
        assert wait_until(
            lambda: router.replicas[victim].last_poll > before, timeout=3
        )
        assert router._poll_thread.is_alive()
    finally:
        _teardown(replicas, router)


# ======================================================================
# Fleet-wide distributed tracing (ISSUE 12): hop-context propagation,
# per-attempt spans, /debug/spans, timeline assembly
# ======================================================================


def _post_with_headers(port, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _load_trace_assemble():
    from tools import trace_assemble

    return trace_assemble


def test_trace_context_propagates_and_roots_replica_tree():
    """Every dial carries X-Trace-Context; the replica adopts its trace
    id and roots its request span under the router's attempt span — the
    cross-process link the assembler joins on."""
    from k8s_device_plugin_tpu.utils.spans import parse_trace_context

    replicas, router, _ = _fleet(2)
    try:
        prompt = [41, 42, 43, 44]
        got = _post_with_headers(
            router.port,
            {"prompt": prompt, "max_new_tokens": 3},
            headers={"X-Request-Id": "propagate-1"},
        )
        assert got["trace_id"] == "propagate-1"
        served = next(r for r in replicas if r.seen_trace_context)
        ctx = parse_trace_context(served.seen_trace_context[-1])
        assert ctx is not None, served.seen_trace_context
        assert ctx.trace_id == "propagate-1"
        assert ctx.hop == 1 and ctx.attempt == 0
        # The parent span id resolves to a recorded router.attempt span.
        # (The router records its request root in the handler's finally,
        # after the reply is written — the client can see the reply
        # first, so wait for it as for the replica's side below.)
        assert wait_until(
            lambda: any(
                s["name"] == "router.request"
                for s in router.spans.dump(trace_id="propagate-1")["spans"]
            ),
            timeout=5,
        )
        router_spans = router.spans.dump(trace_id="propagate-1")["spans"]
        by_name = {}
        for s in router_spans:
            by_name.setdefault(s["name"], []).append(s)
        assert set(by_name) == {
            "router.request", "router.route", "router.attempt"
        }
        attempt = by_name["router.attempt"][0]
        assert attempt["span_id"] == int(ctx.parent_span, 16)
        assert attempt["parent_id"] == by_name["router.request"][0]["span_id"]
        assert attempt["attrs"]["kind"] == "primary"
        assert attempt["attrs"]["status"] == 200
        assert by_name["router.request"][0]["attrs"]["outcome"] == "ok"
        # Replica side: the request span carries the parent link attrs.
        # (The handler thread records it just after writing the reply —
        # the client can observe the response first, so wait.)
        assert wait_until(
            lambda: served.spans.dump(trace_id="propagate-1")["spans"],
            timeout=5,
        )
        rep_spans = served.spans.dump(trace_id="propagate-1")["spans"]
        root = next(s for s in rep_spans if s["name"] == "request")
        assert root["attrs"]["parent"] == ctx.parent_span
        assert root["attrs"]["hop"] == 1
    finally:
        _teardown(replicas, router)


def test_router_debug_spans_endpoint_and_rid_filter():
    replicas, router, _ = _fleet(2)
    try:
        for rid in ("spans-a", "spans-b"):
            _post_with_headers(
                router.port,
                {"prompt": [7, 8, 9], "max_new_tokens": 2},
                headers={"X-Request-Id": rid},
            )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/spans", timeout=10
        ) as resp:
            full = json.loads(resp.read())
        assert full["name"] == "router" and full["capacity"] > 0
        assert {s["trace_id"] for s in full["spans"]} == {"spans-a", "spans-b"}
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/spans?rid=spans-b",
            timeout=10,
        ) as resp:
            only = json.loads(resp.read())
        assert only["spans"] and all(
            s["trace_id"] == "spans-b" for s in only["spans"]
        )
    finally:
        _teardown(replicas, router)


def test_hedge_legs_are_distinct_linked_child_spans():
    """A hedged unary request produces TWO attempt spans — distinct
    span ids, distinct attempt indexes, kinds primary/hedge — both
    children of the one request root, and each replica saw its own
    X-Trace-Context naming its own leg."""
    from k8s_device_plugin_tpu.utils.spans import parse_trace_context

    fast = FakeReplica().start()
    slow = FakeReplica(prefill_delay_s=1.5).start()
    router = RouterServer(
        [fast.name, slow.name],
        host="127.0.0.1",
        port=0,
        poll_interval_s=0.1,
        hedge=True,
        hedge_min_s=0.1,
        backoff_base_s=0.02,
    ).start()
    try:
        prompt = _home_prompt(router, slow.name)
        got = _post_with_headers(
            router.port,
            {"prompt": prompt, "max_new_tokens": 4},
            headers={"X-Request-Id": "hedged-1"},
        )
        assert got["tokens"] == fake_generate(prompt, 4)
        # The losing primary leg records its span when its stalled dial
        # finally resolves (the drain thread closes it) — AFTER the
        # client already has the hedge's answer.
        assert wait_until(
            lambda: len(
                [
                    s
                    for s in router.spans.dump(trace_id="hedged-1")["spans"]
                    if s["name"] == "router.attempt"
                ]
            )
            == 2,
            timeout=5,
        )
        spans = router.spans.dump(trace_id="hedged-1")["spans"]
        attempts = [s for s in spans if s["name"] == "router.attempt"]
        assert len(attempts) == 2, attempts
        root = next(s for s in spans if s["name"] == "router.request")
        assert {a["parent_id"] for a in attempts} == {root["span_id"]}
        assert {a["span_id"] for a in attempts} != {root["span_id"]}
        assert len({a["span_id"] for a in attempts}) == 2
        assert {a["attrs"]["attempt"] for a in attempts} == {0, 1}
        assert {a["attrs"]["kind"] for a in attempts} == {"primary", "hedge"}
        # Each replica's received context names ITS leg.
        ctxs = {}
        for r, leg in ((slow, "primary"), (fast, "hedge")):
            ctx = parse_trace_context(r.seen_trace_context[-1])
            assert ctx is not None and ctx.trace_id == "hedged-1"
            ctxs[leg] = ctx
        assert ctxs["primary"].parent_span != ctxs["hedge"].parent_span
        by_kind = {a["attrs"]["kind"]: a for a in attempts}
        for leg, ctx in ctxs.items():
            assert by_kind[leg]["span_id"] == int(ctx.parent_span, 16)
    finally:
        _teardown([fast, slow], router)


def test_killed_stream_assembles_one_timeline_zero_gaps():
    """THE assembly contract on the failover path: kill the replica
    mid-stream, let the stream complete elsewhere, then join router +
    replica span dumps — ONE timeline, two attempts (primary/failover,
    distinct linked span ids), zero orphans/gaps/broken links, and the
    failover-attempt count matches the router's failover metric."""
    ta = _load_trace_assemble()
    replicas, router, _ = _fleet(
        2, token_delay_s=0.02, router_kwargs=dict(breaker_failures=1)
    )
    try:
        victim = replicas[0]
        prompt = _home_prompt(router, victim.name)
        failovers0 = router.metrics.failovers.value()
        import http.client as http_client

        conn = http_client.HTTPConnection(
            "127.0.0.1", router.port, timeout=30
        )
        conn.request(
            "POST", "/generate",
            json.dumps({"prompt": prompt, "max_new_tokens": 10,
                        "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "killed-1"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        events = []
        killed = False
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            event = json.loads(line[5:].strip())
            events.append(event)
            if len(events) == 3 and not killed:
                victim.kill()
                killed = True
            if event.get("done"):
                break
        conn.close()
        assert events and events[-1].get("done")
        assert events[-1]["tokens"] == fake_generate(prompt, 10)
        assert router.metrics.failovers.value() == failovers0 + 1
        # Both replica handler threads record their request spans just
        # AFTER the client observes the stream end (the victim's when
        # its next write hits the reset socket): wait for the rings.
        assert wait_until(
            lambda: all(
                r.spans.dump(trace_id="killed-1")["spans"]
                for r in replicas
            ),
            timeout=5,
        )
        # So does the router's handler its request root, in its finally
        # after the stream's last write: wait for it too.
        assert wait_until(
            lambda: any(
                s["name"] == "router.request"
                for s in router.spans.dump(trace_id="killed-1")["spans"]
            ),
            timeout=5,
        )
        # Assemble: router ring fetched LIVE (?rid= narrows server-side),
        # the dead victim's ring read from its in-process recorder (the
        # post-mortem dump shape), the survivor's over HTTP.
        sources = ta.fetch_url(
            f"http://127.0.0.1:{router.port}/debug/spans", rid="killed-1"
        )
        sources += ta._as_source("victim", victim.spans.dump())
        sources += ta.fetch_url(
            f"http://127.0.0.1:{replicas[1].port}/debug/spans",
            rid="killed-1",
        )
        timelines = ta.assemble(sources, trace_id="killed-1")
        assert len(timelines) == 1
        t = timelines[0]
        assert t["complete"], ta.render_text(t)
        assert not t["orphans"] and not t["gaps"] and not t["broken_links"]
        kinds = [a["kind"] for a in t["attempts"]]
        assert kinds == ["primary", "failover"], kinds
        assert len({a["span_id"] for a in t["attempts"]}) == 2
        # Attempt count matches what the router metered: 1 first dial +
        # 1 failover.
        n_failover_attempts = sum(
            1 for a in t["attempts"] if a["kind"] == "failover"
        )
        assert n_failover_attempts == router.metrics.failovers.value()
        # The victim's half shows the cut; the survivor's the finish.
        assert t["attempts"][0]["replica_trees"][0]["attrs"]["outcome"] == "cut"
        assert (
            t["attempts"][1]["replica_trees"][0]["attrs"]["outcome"]
            == "completed"
        )
        # Completeness detection feeds chaos scoring.
        det = ta.completeness_detections(timelines, {"killed-1": 2})
        assert len(det) == 1 and det[0]["rid"] == "killed-1"
    finally:
        _teardown(replicas, router)


# ======================================================================
# Elastic fleet (ISSUE 14): migration planner, planned moves, /debug/fleet
# ======================================================================


def test_migration_planner_sustained_hot_budget_and_cooldown():
    """Planner units on a fake clock: no plan before `sustain_polls`
    consecutive hot polls, the hottest source pairs with the coldest
    target, the token-bucket budget paces moves, and the per-source
    cooldown blocks immediate re-planning."""
    from k8s_device_plugin_tpu.router.migration import (
        MigrationConfig,
        MigrationPlanner,
    )

    t = [100.0]
    cfg = MigrationConfig(
        hot_wait_s=1.0, cold_wait_s=0.3, sustain_polls=3,
        budget=2.0, refill_per_s=1.0, max_moves_per_plan=2,
        cooldown_s=10.0,
    )
    pl = MigrationPlanner(cfg, now=lambda: t[0])

    def sweep(hot_wait=5.0, cold_wait=0.1):
        pl.observe("hot:1", wait_ewma_s=hot_wait, drain_rate_rps=None,
                   queue_depth=8, eligible=True)
        pl.observe("cold:1", wait_ewma_s=cold_wait, drain_rate_rps=None,
                   queue_depth=0, eligible=True)

    sweep()
    assert pl.plan() is None  # 1 hot poll: not sustained
    sweep()
    assert pl.plan() is None  # 2: still not
    sweep()
    assert pl.plan() == ("hot:1", "cold:1", 2)  # 3: plan, spends budget
    sweep(), sweep(), sweep()
    assert pl.plan() is None, "budget spent: no plan until refill"
    t[0] += 2.0  # refill 2 tokens — but the 10s cooldown still holds
    sweep()
    assert pl.plan() is None
    t[0] += 10.0
    sweep(), sweep(), sweep()
    assert pl.plan() == ("hot:1", "cold:1", 2)
    # A cool poll resets the streak: hot again needs a full sustain run.
    t[0] += 10.0
    sweep(), sweep()
    sweep(hot_wait=0.0)
    sweep(), sweep()
    assert pl.plan() is None
    sweep()
    assert pl.plan() is not None


def test_migration_planner_requires_cold_target_and_eligibility():
    """Fleet-wide hot is a SCALE signal, not a migration: no cold peer
    -> no plan.  Ineligible replicas (draining/fenced/unreachable) are
    neither sources nor targets, and pressure falls back to the
    queue-depth/drain-rate forecast when no EWMA is exported."""
    from k8s_device_plugin_tpu.router.migration import (
        MigrationConfig,
        MigrationPlanner,
        replica_pressure,
    )

    t = [0.0]
    pl = MigrationPlanner(
        MigrationConfig(hot_wait_s=1.0, cold_wait_s=0.3, sustain_polls=1),
        now=lambda: t[0],
    )
    # Both hot: nowhere to move.
    pl.observe("a:1", wait_ewma_s=5.0, drain_rate_rps=None,
               queue_depth=9, eligible=True)
    pl.observe("b:1", wait_ewma_s=4.0, drain_rate_rps=None,
               queue_depth=9, eligible=True)
    assert pl.plan() is None
    # A cold peer exists but is fenced (ineligible): still no plan.
    pl.observe("b:1", wait_ewma_s=0.1, drain_rate_rps=None,
               queue_depth=0, eligible=False)
    assert pl.plan() is None
    # Eligible cold peer: plan fires, hottest -> coldest.
    pl.observe("b:1", wait_ewma_s=0.1, drain_rate_rps=None,
               queue_depth=0, eligible=True)
    src, dst, n = pl.plan()
    assert (src, dst) == ("a:1", "b:1") and n >= 1
    # Pressure fallback: no EWMA -> queue/drain forecast; no data -> 0.
    assert replica_pressure(None, 2.0, 10) == 5.0
    assert replica_pressure(None, None, 10) == 0.0
    assert replica_pressure(1.5, 2.0, 10) == 1.5
    # Config validation.
    with pytest.raises(ValueError):
        MigrationPlanner(MigrationConfig(hot_wait_s=0.2, cold_wait_s=0.3))
    with pytest.raises(ValueError):
        MigrationPlanner(MigrationConfig(sustain_polls=0))


def test_scale_recommendation_verdicts():
    """scale_up when a hot majority has no cold headroom, scale_down
    only when EVERYONE is cold with empty queues, hold otherwise —
    and never anything but hold without data."""
    from k8s_device_plugin_tpu.router.migration import scale_recommendation

    def row(pressure, depth=0, eligible=True):
        return {"pressure_s": pressure, "queue_depth": depth,
                "eligible": eligible}

    up = scale_recommendation(
        {"a:1": row(5.0, 9), "b:1": row(4.0, 7)},
        hot_wait_s=2.0, cold_wait_s=0.5,
    )
    assert up["action"] == "scale_up"
    assert up["suggested_replicas"] > up["replicas"]
    # Hot majority BUT a cold peer exists: migrate first, hold scale.
    hold = scale_recommendation(
        {"a:1": row(5.0, 9), "b:1": row(4.0, 7), "c:1": row(0.1)},
        hot_wait_s=2.0, cold_wait_s=0.5,
    )
    assert hold["action"] == "hold" and hold["cold"] == ["c:1"]
    down = scale_recommendation(
        {"a:1": row(0.0), "b:1": row(0.1)},
        hot_wait_s=2.0, cold_wait_s=0.5,
    )
    assert down["action"] == "scale_down"
    assert down["suggested_replicas"] == 1
    # Cold but with queued work: hold (the queue says otherwise).
    busy = scale_recommendation(
        {"a:1": row(0.0, 3), "b:1": row(0.1)},
        hot_wait_s=2.0, cold_wait_s=0.5,
    )
    assert busy["action"] == "hold"
    # One replica, cold: never scale below one.
    one = scale_recommendation({"a:1": row(0.0)})
    assert one["action"] == "hold"
    # No eligible data: hold, never a guess.
    none = scale_recommendation({"a:1": row(0.0, eligible=False)})
    assert none["action"] == "hold"


def test_donor_for_picks_adjacent_ring_owner():
    """The warm-up donor is the peer owning the ring segments the
    joiner inherits: deterministic, never the joiner itself, None with
    no peers — and consistent with the router's own ring (the vnode
    scheme and hash are shared)."""
    from k8s_device_plugin_tpu.models.engine_snapshot import donor_for

    peers = [f"10.0.0.{i}:8000" for i in range(1, 6)]
    joiner = "10.0.0.9:8000"
    donor = donor_for(joiner, peers)
    assert donor in peers
    # Deterministic regardless of listing order, joiner excluded.
    assert donor_for(joiner, list(reversed(peers)) + [joiner]) == donor
    assert donor_for(joiner, [joiner]) is None
    assert donor_for(joiner, []) is None
    # The donor really is the plurality owner of the joiner's segments.
    from collections import Counter

    from k8s_device_plugin_tpu.router.ring import HashRing, _hash64

    ring = HashRing(peers, vnodes=64)
    counts = Counter(
        ring.lookup(_hash64(f"{joiner}#{i}".encode())) for i in range(64)
    )
    assert counts[donor] == max(counts.values())


def test_summary_signals_reach_replica_state_and_fleet():
    """The poll loop carries queue_wait_ewma_s / drain_rate_rps into
    ReplicaState, and GET /debug/fleet turns them into per-replica
    pressure plus a scale recommendation (hot fleet -> scale_up)."""
    replicas, router, _ = _fleet(2)
    try:
        for r in replicas:
            r.wait_ewma_s = 4.0
            r.drain_rate_rps = 2.5
        assert wait_until(
            lambda: all(
                st.queue_wait_ewma_s == 4.0 and st.drain_rate_rps == 2.5
                for st in router.replicas.values()
            ),
            timeout=5,
        ), {n: st.snapshot() for n, st in router.replicas.items()}
        fleet = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{router.port}/debug/fleet", timeout=5
            ).read()
        )
        assert set(fleet["replicas"]) == {r.name for r in replicas}
        for row in fleet["replicas"].values():
            assert row["pressure_s"] == 4.0 and row["eligible"]
        assert fleet["recommendation"]["action"] == "scale_up"
        assert fleet["migration"] == {"enabled": False}
        # /debug/router still carries the raw signals per replica.
        snap = router.snapshot()
        assert all(
            st["queue_wait_ewma_s"] == 4.0
            for st in snap["replicas"].values()
        )
    finally:
        _teardown(replicas, router)


def test_planner_driven_migration_zero_drop_bit_identical():
    """End to end through the REAL planner: a sustained-hot replica's
    live stream is planned onto the cold peer at a paced token boundary
    and completes bit-identically — zero client-visible drops, planned
    and done both metered and on the flight timeline."""
    import threading

    from k8s_device_plugin_tpu.router.migration import MigrationConfig

    replicas, router, flight = _fleet(
        2,
        router_kwargs=dict(
            migrate=True,
            migration=MigrationConfig(
                hot_wait_s=1.0, cold_wait_s=0.3, sustain_polls=2,
                budget=4.0, refill_per_s=10.0, cooldown_s=0.2,
                max_moves_per_plan=2,
            ),
        ),
        token_delay_s=0.03,
    )
    try:
        hot = replicas[0]
        cold = replicas[1]
        prompt = _home_prompt(router, hot.name)
        expect = fake_generate(prompt, 30)
        result: dict = {}

        def _run():
            result["events"], result["tokens"] = _stream(
                router.port, {"prompt": prompt, "max_new_tokens": 30},
                timeout=30,
            )

        thread = threading.Thread(target=_run, daemon=True)
        thread.start()
        # Only once the stream is live does the fleet turn hot: the
        # planner needs an actual session to move.
        assert wait_until(lambda: hot.active_streams > 0, timeout=10)
        hot.wait_ewma_s = 5.0
        cold.wait_ewma_s = 0.05
        assert wait_until(
            lambda: router.metrics.migrations.value(outcome="done") >= 1,
            timeout=10,
        ), router.fleet_state()
        thread.join(timeout=30)
        assert result["tokens"] == expect, "migrated stream must be " \
            "bit-identical"
        assert result["events"][-1]["done"]
        # The move really crossed replicas: the cold peer served the
        # continuation as prompt + emitted under the same rid.
        assert cold.generate_requests >= 1
        assert router.metrics.migrations.value(outcome="planned") >= 1
        kinds = [e["kind"] for e in flight.snapshot()["events"]]
        assert "router.migration_planned" in kinds
        assert "router.migration_done" in kinds
        # Zero-drop means zero failovers too: a planned move never
        # counts as (or causes) a death.
        assert router.metrics.failovers.value() == 0
    finally:
        _teardown(replicas, router)


def test_migration_aborts_when_target_breaker_open():
    """The abort contract: a planned move whose target's breaker is
    open stays put — the stream finishes on its home replica,
    bit-identical, with outcome=aborted metered and NO done."""
    replicas, router, flight = _fleet(
        2,
        router_kwargs=dict(
            migrate=True, breaker_open_s=30.0,
        ),
        token_delay_s=0.03,
    )
    try:
        src, target = replicas[0], replicas[1]
        prompt = _home_prompt(router, src.name)
        expect = fake_generate(prompt, 12)
        # Trip the target's breaker (stays open for 30s).
        breaker = router.replicas[target.name].breaker
        for _ in range(5):
            breaker.record_failure()
        assert breaker.state == "open"
        import threading

        result: dict = {}

        def _run():
            result["events"], result["tokens"] = _stream(
                router.port, {"prompt": prompt, "max_new_tokens": 12},
                timeout=30,
            )

        thread = threading.Thread(target=_run, daemon=True)
        thread.start()
        assert wait_until(lambda: src.active_streams > 0, timeout=10)
        assert router.plan_migration(src.name, target=target.name) == 1
        thread.join(timeout=30)
        assert result["tokens"] == expect
        assert router.metrics.migrations.value(outcome="aborted") >= 1
        assert router.metrics.migrations.value(outcome="done") == 0
        aborted = [
            e for e in flight.snapshot()["events"]
            if e["kind"] == "router.migration_aborted"
        ]
        assert aborted and aborted[0]["reason"] == "target_ineligible"
        # The stream never left home.
        assert target.generate_requests == 0
    finally:
        _teardown(replicas, router)


def test_plan_migration_ranks_hottest_prefix_sessions():
    """plan_migration moves the hottest prefix-block sessions first:
    with two sessions live on the source — one shared by two streams,
    one solo — a single-move plan flags a stream of the SHARED prefix."""
    import threading

    replicas, router, _ = _fleet(2, token_delay_s=0.05)
    try:
        src = replicas[0]
        shared = _home_prompt(router, src.name)
        solo = _home_prompt(router, src.name, base=200)
        assert router.policy.key_of(shared) != router.policy.key_of(solo)
        threads = []
        for p in (shared, shared + [7], solo):
            t = threading.Thread(
                target=lambda pp=p: _stream(
                    router.port,
                    {"prompt": pp, "max_new_tokens": 14}, timeout=30,
                ),
                daemon=True,
            )
            t.start()
            threads.append(t)
        assert wait_until(lambda: src.active_streams >= 3, timeout=10)
        assert router.plan_migration(src.name, target=replicas[1].name,
                                     max_moves=1) == 1
        shared_key = router.policy.key_of(shared)
        flagged = [
            c for c in router._streams.values()
            if c.migrate_to == replicas[1].name
            or (c.migrate_to is None and c.replica == replicas[1].name)
        ]
        with router._streams_lock:
            planned_keys = {
                c.prefix_key
                for c in router._streams.values()
                if c.migrate_to is not None
            }
        assert planned_keys == {shared_key}, (planned_keys, flagged)
        for t in threads:
            t.join(timeout=30)
    finally:
        _teardown(replicas, router)


# ======================================================================
# Disaggregated prefill/decode routing (router/disagg.py, ISSUE 15)
# ======================================================================


def test_disagg_policy_classify_and_pick():
    """Pure split-policy units: prompt-length threshold x decode-pool
    pressure, the hot bar, the no-pool degradation, and the
    least-pressure prefill pick."""
    from k8s_device_plugin_tpu.router.disagg import (
        NO_POOL,
        SHORT,
        SPLIT,
        DisaggConfig,
        DisaggPolicy,
        pick_prefill,
    )

    pol = DisaggPolicy(DisaggConfig(
        threshold_tokens=256, hot_threshold_tokens=64, hot_wait_s=0.5
    ))
    assert pol.classify(300, 0.0, True) == SPLIT
    assert pol.classify(100, 0.0, True) == SHORT
    # Hot decode pool drops the bar: the same 100-token prompt splits.
    assert pol.classify(100, 0.9, True) == SPLIT
    assert pol.classify(32, 0.9, True) == SHORT
    # Split-worthy but no healthy prefill replica: unified degradation.
    assert pol.classify(300, 0.0, False) == NO_POOL
    assert pick_prefill({}) is None
    assert pick_prefill({"b:1": 0.2, "a:1": 0.2}) == "a:1"  # tie: name
    assert pick_prefill({"b:1": 0.1, "a:1": 0.2}) == "b:1"
    with pytest.raises(ValueError):
        DisaggConfig(threshold_tokens=8, hot_threshold_tokens=9)


def _disagg_fleet(threshold=32):
    """1 prefill + 2 decode fakes behind a disagg-routing router."""
    from k8s_device_plugin_tpu.router.disagg import DisaggConfig

    pre = FakeReplica(role="prefill", prefix_tokens=16).start()
    decodes = [
        FakeReplica(role="decode", prefix_tokens=16).start()
        for _ in range(2)
    ]
    flight = FlightRecorder(capacity=2048, name="router-test")
    router = RouterServer(
        [r.name for r in decodes],
        host="127.0.0.1",
        port=0,
        flight=flight,
        poll_interval_s=0.1,
        breaker_open_s=0.3,
        backoff_base_s=0.02,
        backoff_max_s=0.2,
        hedge=False,
        upstream_timeout_s=10.0,
        request_timeout_s=30.0,
        disagg=True,
        disagg_config=DisaggConfig(
            threshold_tokens=threshold, hot_threshold_tokens=16
        ),
        prefill_replicas=[pre.name],
    ).start()
    return pre, decodes, router, flight


def test_disagg_split_pulls_prefix_and_stays_off_prefill_ring():
    """A long prompt is stamped with the prefill locator: the decode
    replica pulls the prefix over /v1/prefill (real wire format) and
    serves oracle tokens; the prefill replica never sees /generate and
    owns no ring segments; a short prompt rides unified with the LOCAL
    sentinel."""
    pre, decodes, router, flight = _disagg_fleet()
    try:
        long_prompt = list(range(700, 748))  # 48 >= 32: split
        out = _post(router.port, {"prompt": long_prompt, "max_new_tokens": 5})
        assert out["tokens"] == fake_generate(long_prompt, 5)
        assert pre.prefill_serves == 1
        assert sum(d.handoff_fetches for d in decodes) == 1
        assert sum(d.handoff_fetch_failures for d in decodes) == 0
        assert pre.generate_requests == 0
        served = next(d for d in decodes if d.generate_requests)
        assert served.seen_handoff[-1] == pre.name
        # The split is a flight event + metric verdict.
        assert any(
            e["kind"] == "router.disagg_split" and e["source"] == pre.name
            for e in flight.window(kinds=["router.disagg_split"])
        )
        # Prefill replicas own no ring segments.
        assert pre.name not in router.ring.nodes
        assert router.replicas[pre.name].role == "prefill"
        # Short prompt: unified dispatch, LOCAL sentinel.
        out = _post(router.port, {"prompt": [1, 2, 3], "max_new_tokens": 3})
        assert out["tokens"] == fake_generate([1, 2, 3], 3)
        all_handoff = [h for d in decodes for h in d.seen_handoff if h]
        assert "local" in all_handoff
        # A second session on the same prefix is resident: no new pull.
        _post(router.port, {"prompt": long_prompt[:16] + list(range(60, 92)),
                            "max_new_tokens": 3})
    finally:
        _teardown([pre] + decodes, router)


def test_disagg_stream_split_bit_identical():
    pre, decodes, router, _ = _disagg_fleet()
    try:
        prompt = list(range(800, 848))
        _, tokens = _stream(
            router.port, {"prompt": prompt, "max_new_tokens": 6}
        )
        assert tokens == fake_generate(prompt, 6)
        assert pre.prefill_serves == 1
    finally:
        _teardown([pre] + decodes, router)


def test_disagg_prefill_pool_down_degrades_to_unified():
    """Kill the prefill pool: the router classifies no_pool, stamps the
    LOCAL sentinel, and the decode replicas run their own prefill —
    zero client-visible change."""
    pre, decodes, router, flight = _disagg_fleet()
    try:
        pre.kill()
        assert wait_until(
            lambda: not router.replicas[pre.name].reachable, timeout=5
        )
        prompt = list(range(900, 948))
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        assert out["tokens"] == fake_generate(prompt, 4)
        assert sum(d.handoff_fetches for d in decodes) == 0
        served = next(d for d in decodes if d.generate_requests)
        assert served.seen_handoff[-1] == "local"
        assert served.cold_prefills >= 1  # local prefill paid locally
    finally:
        _teardown([pre] + decodes, router)


def test_disagg_dead_source_mid_routing_degrades_to_local_prefill():
    """The locator names a prefill replica that dies before the pull:
    the decode replica's fetch fails, it degrades to local prefill, and
    the client still gets oracle tokens — plus a handoff.fetch_failed
    flight event on exactly the serving decode replica."""
    pre, decodes, router, _ = _disagg_fleet()
    try:
        # Kill the prefill replica AFTER the router polled it healthy:
        # classification still stamps its locator, the pull fails.
        pre.kill()
        prompt = list(range(950, 998))
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 4})
        assert out["tokens"] == fake_generate(prompt, 4)
        served = next(d for d in decodes if d.generate_requests)
        assert served.handoff_fetch_failures == 1
        assert served.flight.window(kinds=["handoff.fetch_failed"])
        other = next(d for d in decodes if d is not served)
        assert other.handoff_fetch_failures == 0
    finally:
        _teardown([pre] + decodes, router)


def test_decode_409_without_disagg_walks_to_unified_replica():
    """A decode-role replica in a fleet WITHOUT --disagg answers 409 +
    X-Prefill-Needed for a cold long prompt; the router skips it (no
    breaker hit) and a unified replica serves — the refusal is metered,
    never a client error."""
    dec = FakeReplica(role="decode", prefix_tokens=16).start()
    uni = FakeReplica().start()
    flight = FlightRecorder(capacity=512, name="router-test")
    router = RouterServer(
        [dec.name, uni.name], host="127.0.0.1", port=0, flight=flight,
        poll_interval_s=0.1, hedge=False, backoff_base_s=0.02,
        backoff_max_s=0.2, request_timeout_s=20.0,
    ).start()
    try:
        # Enough attempts that at least one homes on the decode replica.
        for base in (100, 400, 900):
            prompt = [base + i for i in range(48)]
            out = _post(router.port, {"prompt": prompt, "max_new_tokens": 3})
            assert out["tokens"] == fake_generate(prompt, 3)
        if dec.prefill_refusals:
            assert flight.window(kinds=["router.prefill_needed"])
            # A 409 is a routing verdict, not a fault: breaker closed.
            state = router.replicas[dec.name].breaker.snapshot()["state"]
            assert state == "closed"
    finally:
        _teardown([dec, uni], router)


def test_disagg_role_discovered_by_poll_reconciles_ring():
    """A replica added as unified whose summary later reports
    role=prefill leaves the /generate ring (and rejoins when it flips
    back) — the redeploy-flip path."""
    a = FakeReplica().start()
    b = FakeReplica().start()
    flight = FlightRecorder(capacity=512, name="router-test")
    router = RouterServer(
        [a.name, b.name], host="127.0.0.1", port=0, flight=flight,
        poll_interval_s=0.05, hedge=False,
    ).start()
    try:
        assert set(router.ring.nodes) == {a.name, b.name}
        a.role = "prefill"
        assert wait_until(lambda: a.name not in router.ring.nodes, timeout=5)
        assert router.replicas[a.name].role == "prefill"
        assert any(
            e["kind"] == "router.replica_role" and e["role"] == "prefill"
            for e in flight.window(kinds=["router.replica_role"])
        )
        a.role = "unified"
        assert wait_until(lambda: a.name in router.ring.nodes, timeout=5)
    finally:
        _teardown([a, b], router)


# ---------------------------------------------------------------------------
# Fleet-wide content-addressed KV fabric (router/fabric.py, --fabric):
# bloom-advertised locator, any-peer pulls, K-replica hot-prefix
# replication.  All jax-free: FakeReplica advertises real PrefixBloom
# digests and serves /v1/prefill in the real wire format.


def _bloom_wire(prefixes, page_size=16, root=-1):
    """A fabric_digest wire dict advertising the given token prefixes."""
    from k8s_device_plugin_tpu.utils.prefixbloom import PrefixBloom

    bloom = PrefixBloom()
    for p in prefixes:
        bloom.add(root, p)
    wire = bloom.to_wire()
    wire["page_size"] = page_size
    return wire


def test_fabric_locator_coverage_best_owner_and_forget():
    """FabricLocator resolves the deepest page-aligned advertised
    prefix per replica, deterministic name tie-break, and drops views
    on absent/unparseable digests and membership removal."""
    from k8s_device_plugin_tpu.router.fabric import FabricLocator

    loc = FabricLocator(16)
    prompt = list(range(100, 148))  # 3 full 16-token pages
    assert loc.update("a", _bloom_wire([prompt[:16], prompt[:32]])) == 2
    assert loc.update(
        "b", _bloom_wire([prompt[:16], prompt[:32], prompt[:48]])
    ) == 3
    assert loc.update("c", {"bogus": 1}) == 0  # unparseable: no view
    assert loc.coverage("a", prompt) == 32
    assert loc.coverage("b", prompt) == 48
    assert loc.coverage("c", prompt) == 0
    assert loc.best_owner(prompt, ["a", "b", "c"]) == ("b", 48)
    # Equal depth ties break toward the smaller name: stable stamping.
    loc.update("b", _bloom_wire([prompt[:16], prompt[:32]]))
    assert loc.best_owner(prompt, ["b", "a"]) == ("a", 32)
    # owners() is the FULL-prefix census the replicator counts.
    assert loc.owners(prompt[:32], ["a", "b"]) == ["a", "b"]
    assert loc.owners(prompt, ["a", "b"]) == []
    # A poll with no digest clears the view; forget drops it outright.
    assert loc.update("a", None) == 0
    assert loc.coverage("a", prompt) == 0
    loc.forget("b")
    assert loc.advertised_roots() == {}


def test_fabric_replicator_k_copies_ledger_and_cold_eviction():
    """FabricReplicator plans one bounded pull for a hot prefix whose
    owner runs hot, counts the unconfirmed copy toward K (no duplicate
    while digests lag), and drops ONLY the router-created copy after
    the prefix goes cold."""
    from k8s_device_plugin_tpu.router.fabric import (
        FabricConfig,
        FabricLocator,
        FabricReplicator,
    )

    loc = FabricLocator(16)
    prefix = tuple(range(200, 232))  # 2 full pages
    loc.update("a", _bloom_wire([list(prefix)[:16], list(prefix)]))
    cfg = FabricConfig(
        replicate_k=2, hot_wait_s=1.0, cold_wait_s=0.2,
        hot_score=2.0, cold_sweeps=2, confirm_sweeps=3,
    )
    rep = FabricReplicator(cfg)
    hot = {prefix: 1}  # 1 live stream x 2 pages = 2.0 >= hot_score
    pressures = {"a": 5.0, "b": 0.0, "c": 0.1}
    assert rep.plan(loc, hot, pressures) == [{
        "op": "pull", "target": "b", "source": "a",
        "prompt": list(prefix), "streams": 1, "pages": 2,
    }]
    # The planned copy counts toward K until confirmed: no duplicate.
    assert rep.plan(loc, hot, pressures) == []
    # The pull lands and the target's digest confirms the copy.
    loc.update("b", _bloom_wire([list(prefix)[:16], list(prefix)]))
    assert rep.plan(loc, hot, pressures) == []
    # Cold: after cold_sweeps zero-stream sweeps the ROUTER-CREATED
    # copy is dropped; the traffic-warmed owner "a" keeps its own.
    assert rep.plan(loc, {}, pressures) == []  # streak 1 of 2
    assert rep.plan(loc, {}, pressures) == [
        {"op": "drop", "target": "b", "prompt": list(prefix)}
    ]
    assert rep.snapshot()["ledger"] == []
    # Comfortable owners never trigger copies (affinity already works).
    assert FabricReplicator(cfg).plan(
        loc, hot, {"a": 0.3, "b": 0.0}
    ) == []
    # No cold target = no copy (a scale signal, not an action).
    assert FabricReplicator(cfg).plan(
        loc, hot, {"a": 5.0, "b": 2.0}
    ) == []


def _fabric_prompt_on(router, replica_name, prefix, base=500):
    """A prompt sharing ``prefix`` whose ring home is ``replica_name``
    (the suffix block varies the affinity key, the shared prefix does
    not)."""
    for salt in range(base, base + 500):
        prompt = list(prefix) + [salt] * 16
        if router.ring.order(router.policy.key_of(prompt))[0] == replica_name:
            return prompt
    raise AssertionError(f"no prompt with that prefix homes on {replica_name}")


def test_fabric_stamps_any_peer_source_and_pulls_once():
    """The tentpole path: replica A warms a prefix through ordinary
    traffic and advertises it on the poll; a request for the same
    prefix homed on B gets A stamped as X-Handoff-Source (+ the
    resident-only fabric header); B pulls the prefix over the REAL
    /v1/prefill wire exactly once and later requests are resident —
    the shared prefix is prefilled once fleet-wide."""
    replicas, router, flight = _fleet(
        3,
        router_kwargs={"fabric": True, "racecheck": True},
        prefix_tokens=16,
    )
    a, b = replicas[0], replicas[1]
    try:
        prompt = _home_prompt(router, a.name, length=32)
        out = _post(router.port, {"prompt": prompt, "max_new_tokens": 3})
        assert out["tokens"] == fake_generate(prompt, 3)
        assert a.cold_prefills == 1  # first touch prefills locally
        assert wait_until(
            lambda: router.fabric.advertised_roots().get(a.name, 0) >= 1,
            timeout=5,
        )
        # Same 16-token prefix, different suffix, homed on B.
        p2 = _fabric_prompt_on(router, b.name, prompt[:16])
        out = _post(router.port, {"prompt": p2, "max_new_tokens": 3})
        assert out["tokens"] == fake_generate(p2, 3)
        assert b.seen_fabric_sources[-1] == a.name
        assert b.handoff_fetches == 1 and b.handoff_fetch_failures == 0
        assert a.prefill_serves == 1
        assert b.cold_prefills == 0  # the pull REPLACED the local prefill
        assert any(
            e["source"] == a.name and e["target"] == b.name
            for e in flight.window(kinds=["router.fabric_locate"])
        )
        # Third request, same prefix, same home: now resident on B —
        # no new pull, no new serve.
        out = _post(router.port, {"prompt": p2, "max_new_tokens": 2})
        assert out["tokens"] == fake_generate(p2, 2)
        assert b.handoff_fetches == 1
        # Surfaces: GET /debug/fabric + the /debug/fleet fabric block.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/debug/fabric", timeout=10
        ) as resp:
            state = json.loads(resp.read())
        assert state["enabled"] and state["cross_peer_hits"] >= 1
        assert a.name in state["replicas"]
        fleet = router.fleet_state()["fabric"]
        assert fleet["enabled"]
        assert fleet["advertised_roots"].get(a.name, 0) >= 1
        assert 0.0 < fleet["cross_peer_hit_rate"] <= 1.0
    finally:
        _teardown(replicas, router)


def test_fabric_stale_locator_degrades_to_local_prefill():
    """A stale digest (the owner advertised, then evicted) stamps a
    source that refuses the resident-only pull: the target degrades to
    LOCAL prefill and the client stream is oracle-identical — the
    fabric can waste a fetch, never corrupt an answer."""
    replicas, router, _ = _fleet(
        3, router_kwargs={"fabric": True}, prefix_tokens=16
    )
    a, b = replicas[0], replicas[1]
    try:
        prompt = _home_prompt(router, a.name, length=32)
        _post(router.port, {"prompt": prompt, "max_new_tokens": 2})
        assert wait_until(
            lambda: router.fabric.advertised_roots().get(a.name, 0) >= 1,
            timeout=5,
        )
        # Freeze A's advertisement, then evict its working set: the
        # locator keeps naming A while A can no longer serve.
        stale = a.fabric_digest()
        a.fabric_digest = lambda: stale
        with a._lock:
            a.warm_prefixes.clear()
        p2 = _fabric_prompt_on(router, b.name, prompt[:16])
        out = _post(router.port, {"prompt": p2, "max_new_tokens": 3})
        assert out["tokens"] == fake_generate(p2, 3)  # bit-identical
        assert b.handoff_fetch_failures == 1
        assert a.prefill_refusals >= 1  # resident-only 409, no probe
        assert b.cold_prefills >= 1  # the local-prefill degradation
    finally:
        _teardown(replicas, router)


def test_fabric_replication_copies_hot_prefix_then_evicts_cold():
    """The replication plane end-to-end: a live stream on a hot owner
    triggers ONE proactive copy to the coldest peer (the engine-side
    /debug/fabric/pull wire), the ledger caps fan-out at K, and the
    router-created copy is dropped once the prefix goes cold."""
    from k8s_device_plugin_tpu.router.fabric import FabricConfig

    replicas, router, flight = _fleet(
        3,
        router_kwargs={
            "fabric": True,
            "fabric_config": FabricConfig(
                replicate_k=2, hot_wait_s=0.5, cold_wait_s=0.2,
                hot_score=2.0, cold_sweeps=2, confirm_sweeps=50,
                pull_timeout_s=10.0,
            ),
        },
        prefix_tokens=32,
        token_delay_s=0.06,
    )
    a = replicas[0]
    others = replicas[1:]
    try:
        prompt = _home_prompt(router, a.name, length=32)
        with a._lock:
            a.warm_prefixes.add(tuple(prompt))  # traffic-warmed owner
        a.wait_ewma_s = 5.0  # the owner runs hot (host-side signal)
        assert wait_until(
            lambda: router.fabric.advertised_roots().get(a.name, 0) >= 2,
            timeout=5,
        )
        import threading as _threading

        t = _threading.Thread(
            target=lambda: _stream(
                router.port, {"prompt": prompt, "max_new_tokens": 50}
            ),
        )
        t.start()
        try:
            # 1 stream x 2 pages = hot_score: one pull lands on the
            # colder peer through the engine admin endpoint.
            assert wait_until(
                lambda: sum(r.fabric_pulls for r in others) == 1,
                timeout=5,
            )
            target = next(r for r in others if r.fabric_pulls)
            assert tuple(prompt) in target.warm_prefixes
            assert a.prefill_serves == 1  # pulled FROM the hot owner
            # K=2 satisfied (ledger + digest): no further fan-out.
            time.sleep(0.5)
            assert sum(r.fabric_pulls for r in others) == 1
            assert any(
                e["ok"] and e["target"] == target.name
                for e in flight.window(kinds=["router.fabric_replicated"])
            )
        finally:
            t.join()
        # Stream over: the prefix goes cold and the router drops the
        # copy IT created — the owner's own copy stays.
        assert wait_until(lambda: target.fabric_drops == 1, timeout=5)
        assert tuple(prompt) not in target.warm_prefixes
        assert tuple(prompt) in a.warm_prefixes
        assert flight.window(kinds=["router.fabric_dropped"])
        assert router.fabric_state()["replication"]["pulls_planned"] == 1
    finally:
        _teardown(replicas, router)


def test_metrics_lint_clean_on_live_router_with_fabric_lit():
    """The strict exposition lint against a router whose fabric plane
    has actually resolved (locator families populated): the closed
    verdict enums stay inside their FAMILY_BUDGETS rows."""
    metrics_lint = _load_metrics_lint()
    replicas, router, _ = _fleet(
        2, router_kwargs={"fabric": True}, prefix_tokens=16
    )
    a, b = replicas
    try:
        prompt = _home_prompt(router, a.name, length=32)
        _post(router.port, {"prompt": prompt, "max_new_tokens": 2})
        assert wait_until(
            lambda: router.fabric.advertised_roots().get(a.name, 0) >= 1,
            timeout=5,
        )
        p2 = _fabric_prompt_on(router, b.name, prompt[:16])
        _post(router.port, {"prompt": p2, "max_new_tokens": 2})  # hit
        _post(router.port, {"prompt": p2, "max_new_tokens": 2})
        errors = metrics_lint.lint_url(
            f"http://127.0.0.1:{router.port}/metrics"
        )
        assert errors == [], errors
    finally:
        _teardown(replicas, router)


def test_fleet_plan_renders_fabric_columns():
    """tools/fleet_plan.py grew the locator view (ISSUE 18): the
    per-replica kv_roots column, the cross-peer hit-rate line, and the
    hottest-prefix replication factors render from /debug/fleet —
    live for the locator numbers, synthetic for the hottest-prefix
    rows (they require an in-flight stream); a fabric-less fleet
    renders the disabled line, not a crash."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "fleet_plan", os.path.join(repo, "tools", "fleet_plan.py")
    )
    fleet_plan = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_plan)

    replicas, router, _ = _fleet(
        2, router_kwargs={"fabric": True}, prefix_tokens=16
    )
    a, b = replicas
    try:
        prompt = _home_prompt(router, a.name, length=32)
        _post(router.port, {"prompt": prompt, "max_new_tokens": 2})
        assert wait_until(
            lambda: router.fabric.advertised_roots().get(a.name, 0) >= 1,
            timeout=5,
        )
        p2 = _fabric_prompt_on(router, b.name, prompt[:16])
        _post(router.port, {"prompt": p2, "max_new_tokens": 2})  # pull
        fleet = router.fleet_state()
        out = fleet_plan.render(fleet)
        assert "kv_roots" in out
        assert "fabric: cross-peer hit rate" in out
        # The owner's row carries its advertised-root count.
        owner_row = next(
            line for line in out.splitlines() if line.startswith(a.name)
        )
        assert f" {fleet['fabric']['advertised_roots'][a.name]} " in (
            owner_row + " "
        )
        # Hottest-prefix rows (live streams) rendered from a snapshot.
        fleet["fabric"]["hottest_prefixes"] = [
            {"prefix_tokens": 16, "streams": 3, "replication_factor": 2}
        ]
        out = fleet_plan.render(fleet)
        assert "hot prefix 16 tokens: 3 streams, K=2" in out
    finally:
        _teardown(replicas, router)
    # A fabric-less fleet renders the disabled line.
    bare = fleet_plan.render({"replicas": {}, "slo": {"enabled": False}})
    assert "fabric: disabled" in bare
