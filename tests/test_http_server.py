"""HTTP serving front-end (models/http_server.py): handler threads submit
into the engine while the owner loop steps — the topology the engine's
thread-safety contract exists for.  Oracle everywhere: greedy responses
must equal the dense greedy decode token for token."""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.http_server import EngineServer
from k8s_device_plugin_tpu.models.transformer import (
    GPTConfig,
    PagedConfig,
    TransformerLM,
    greedy_generate,
)
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry
from k8s_device_plugin_tpu.utils.spans import SpanRecorder


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=32)
    rng = jax.random.PRNGKey(0)
    params = TransformerLM(cfg).init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    paged = PagedConfig(page_size=4, num_pages=32, max_pages_per_seq=8)
    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params, paged, max_slots=3, metrics=EngineMetrics(registry),
        spans=SpanRecorder(),
        # The serving-CLI default: overload control ON.  The module's
        # default-priority deadline-free traffic is bit-identical either
        # way (pinned in tests/test_overload.py), so every oracle test
        # here ALSO exercises the controller-on admission path.
        overload=True,
        # The serving-CLI default: the SLO plane ON, so every request
        # through this module also exercises the verdict/usage seam.
        slo=True,
    )
    server = EngineServer(
        engine, host="127.0.0.1", port=0, registry=registry,
        request_timeout_s=120, enable_trace=True,
    ).start()
    yield cfg, params, server
    server.stop()


def _post_path(port, path, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _post(port, payload, timeout=120):
    return _post_path(port, "/generate", payload, timeout)


def _oracle(cfg, params, prompt, n):
    out = greedy_generate(cfg, params, jnp.asarray(prompt, jnp.int32)[None, :], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_generate_matches_oracle(served):
    cfg, params, server = served
    prompt = [3, 141, 59]
    got = _post(server.port, {"prompt": prompt, "max_new_tokens": 6})
    assert got["tokens"] == _oracle(cfg, params, prompt, 6)


def test_concurrent_requests_all_correct(served):
    cfg, params, server = served
    prompts = [[3, 141, 59], [400, 2, 2, 17], [9], [7, 7, 3], [5, 6]]
    results: dict[int, list] = {}
    errs: list = []

    def worker(i):
        try:
            results[i] = _post(
                server.port, {"prompt": prompts[i], "max_new_tokens": 5}
            )["tokens"]
        except Exception as e:  # pragma: no cover - surfaced via assert
            errs.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    for i, p in enumerate(prompts):
        assert results[i] == _oracle(cfg, params, p, 5), (i, p)


def test_sampler_args_flow_through(served):
    cfg, params, server = served
    prompt = [3, 141, 59]
    got = _post(
        server.port,
        {
            "prompt": prompt,
            "max_new_tokens": 5,
            "temperature": 9.0,
            "top_k": 1,
        },
    )
    assert got["tokens"] == _oracle(cfg, params, prompt, 5)


def test_validation_and_errors(served):
    _, _, server = served
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": [], "max_new_tokens": 4})
    assert e.value.code == 422
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"max_new_tokens": 4})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": [1, 2], "max_new_tokens": 10_000})
    assert e.value.code == 422
    # Non-list prompt must come back as a 400, not a dropped connection.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": 5, "max_new_tokens": 4})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": [[1]], "max_new_tokens": 4})
    assert e.value.code == 400


def test_healthz_and_metrics(served):
    _, _, server = served
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/healthz", timeout=30
    ) as r:
        assert r.status == 200
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=30
    ) as r:
        text = r.read().decode()
    assert "tpu_engine_requests_total" in text


def _post_stream(port, payload, timeout=120):
    """POST with stream=true; return the parsed SSE events in order."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({**payload, "stream": True}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    events = []
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
                if events[-1].get("done") or events[-1].get("error"):
                    break
    return events


def test_stream_events_match_oracle(served):
    """SSE: one event per token, in order, then the done event carrying
    the full greedy sequence — identical to the non-streaming oracle."""
    cfg, params, server = served
    prompt = [3, 141, 59]
    want = _oracle(cfg, params, prompt, 7)
    events = _post_stream(server.port, {"prompt": prompt, "max_new_tokens": 7})
    toks = [e["token"] for e in events if "token" in e]
    assert toks == want
    assert [e["index"] for e in events if "token" in e] == list(range(7))
    done = events[-1]
    assert done.get("done") is True and done["tokens"] == want


def test_stream_disconnect_cancels(served):
    """Dropping the SSE connection mid-generation cancels the request:
    the slot and its pages return to the pool (no orphaned decode)."""
    import http.client

    cfg, params, server = served
    engine = server.engine
    free_before = len(engine.free_pages)
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
    conn.request(
        "POST",
        "/generate",
        json.dumps(
            {"prompt": [9, 10], "max_new_tokens": 24, "stream": True}
        ),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    # Read a couple of events to ensure the request is mid-flight...
    got_one = False
    while not got_one:
        line = resp.fp.readline().decode().strip()
        if line.startswith("data: ") and "token" in json.loads(line[6:]):
            got_one = True
    # ...then vanish (the response owns the socket after getresponse).
    resp.close()
    conn.close()
    # The handler thread notices on its next write, cancels, and the
    # owner loop tears the slot down at its next step.
    deadline = time.time() + 60
    while time.time() < deadline:
        if (
            all(s is None for s in engine.slots)
            and len(engine.free_pages) == free_before
        ):
            break
        time.sleep(0.1)
    else:
        raise AssertionError(
            f"cancelled request did not release its slot/pages "
            f"(slots={engine.slots}, free={len(engine.free_pages)}, "
            f"want {free_before})"
        )


def test_logprobs_in_response_and_stream(served):
    """logprobs=true: the JSON reply carries per-token logprobs parallel
    to tokens; stream events carry a logprob field; values are finite
    negatives and the greedy token's logprob is the row max."""
    cfg, params, server = served
    prompt = [3, 141, 59]
    out = _post(
        server.port,
        {"prompt": prompt, "max_new_tokens": 5, "logprobs": True},
    )
    assert len(out["logprobs"]) == len(out["tokens"]) == 5
    assert all(lp <= 0.0 for lp in out["logprobs"])
    # Greedy: every reported logprob must be the max over the vocab of
    # the model's log-softmax at that position (replay densely).
    ctx = list(prompt)
    for tok, lp in zip(out["tokens"], out["logprobs"]):
        logits = TransformerLM(cfg).apply(
            {"params": params}, jnp.asarray([ctx], jnp.int32)
        )[0, -1]
        ls = jax.nn.log_softmax(logits.astype(jnp.float32))
        np.testing.assert_allclose(lp, float(ls[tok]), rtol=1e-4, atol=1e-4)
        assert tok == int(jnp.argmax(ls))
        ctx.append(tok)
    events = _post_stream(
        server.port,
        {"prompt": prompt, "max_new_tokens": 5, "logprobs": True},
    )
    toks = [e for e in events if "token" in e]
    assert all("logprob" in e for e in toks)
    np.testing.assert_allclose(
        [e["logprob"] for e in toks], out["logprobs"], rtol=1e-6
    )


def test_stop_sequences_over_http_and_stream(served):
    """'stop' ends generation with the matched suffix excluded — and the
    STREAM never emits a token the final truncation removes (held back
    by the stop-length lag)."""
    cfg, params, server = served
    prompt = [3, 141, 59]
    want = _oracle(cfg, params, prompt, 8)
    stop = [want[2], want[3]]
    first = next(i for i in range(len(want) - 1) if want[i : i + 2] == stop)
    out = _post(
        server.port,
        {"prompt": prompt, "max_new_tokens": 8, "stop": [stop]},
    )
    assert out["tokens"] == want[:first]
    events = _post_stream(
        server.port,
        {"prompt": prompt, "max_new_tokens": 8, "stop": [stop]},
    )
    streamed = [e["token"] for e in events if "token" in e]
    done = events[-1]
    assert done.get("done") is True
    assert streamed == done["tokens"] == want[:first]


def test_debug_trace_endpoint(served):
    """POST /debug/trace captures a jax.profiler trace of the live loop
    and replies with the dir (which must contain profile output)."""
    import os

    cfg, params, server = served
    # Keep the engine busy so the trace has device work in it.
    bg = threading.Thread(
        target=lambda: _post(
            server.port, {"prompt": [3, 141, 59], "max_new_tokens": 12}
        ),
        daemon=True,
    )
    bg.start()
    out = _post_path(server.port, "/debug/trace", {"seconds": 0.3})
    tdir = out["trace_dir"]  # server-chosen: clients cannot aim writes
    found = [
        os.path.join(r, f) for r, _, fs in os.walk(tdir) for f in fs
    ]
    assert found, "profiler wrote nothing into the trace dir"
    # Malformed bodies answer 400, not a dropped connection.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post_path(server.port, "/debug/trace", [1])
    assert e.value.code == 400
    bg.join(timeout=60)


def test_debug_trace_gated_off_by_default():
    """A default-constructed server must 404 /debug/trace: the endpoint
    is an unauthenticated profiler trigger and is strictly opt-in."""
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = ServingEngine(
        cfg, params, PagedConfig(page_size=4, num_pages=16, max_pages_per_seq=8)
    )
    server = EngineServer(engine, host="127.0.0.1", port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_path(server.port, "/debug/trace", {"seconds": 0.1})
        assert e.value.code == 404
        # Same opt-in gates the single-step profiler capture.
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_path(server.port, "/debug/profile/capture", {"steps": 1})
        assert e.value.code == 404
    finally:
        server.stop()


def test_n_choices_sampling(served):
    """n=3 returns three independent sampled choices over one shared
    prompt; greedy n-copies are identical; n+stream rejects."""
    cfg, params, server = served
    out = _post(
        server.port,
        {"prompt": [3, 141, 59], "max_new_tokens": 6, "n": 3,
         "temperature": 1.2},
    )
    assert len(out["choices"]) == 3
    assert out["tokens"] == out["choices"][0]["tokens"]
    for c in out["choices"]:
        assert len(c["tokens"]) == 6
    rids = {c["rid"] for c in out["choices"]}
    assert len(rids) == 3
    greedy = _post(
        server.port,
        {"prompt": [3, 141, 59], "max_new_tokens": 5, "n": 2},
    )
    assert greedy["choices"][0]["tokens"] == greedy["choices"][1]["tokens"]
    assert greedy["tokens"] == _oracle(cfg, params, [3, 141, 59], 5)
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": [3], "max_new_tokens": 2, "n": 2,
                            "stream": True})
    assert e.value.code == 422
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": [3], "max_new_tokens": 2, "n": 99})
    assert e.value.code == 422


def _post_raw(port, payload, headers=None, timeout=120):
    """POST /generate returning (parsed body, response headers)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _get_json(port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def test_client_trace_id_echoed_and_traced(served):
    """The X-Request-Id contract end to end: a client-supplied id comes
    back on the response header AND body, and the request's span tree —
    >= 3 children (queue, prefill, decode) nested under one root — is
    retrievable from /debug/state under that id."""
    cfg, params, server = served
    tid = "acceptance-trace-0001"
    out, headers = _post_raw(
        server.port,
        {"prompt": [3, 141, 59], "max_new_tokens": 5},
        headers={"X-Request-Id": tid},
    )
    assert out["trace_id"] == tid
    assert headers.get("X-Request-Id") == tid
    assert out["tokens"] == _oracle(cfg, params, [3, 141, 59], 5)
    state = _get_json(server.port, "/debug/state")
    mine = [s for s in state["spans"] if s["trace_id"] == tid]
    root = [s for s in mine if s["name"] == "request"]
    assert len(root) == 1
    children = {
        s["name"] for s in mine if s["parent_id"] == root[0]["span_id"]
    }
    assert {"queue", "prefill", "decode"} <= children
    assert len(children) >= 3
    # Engine snapshot rides along, shaped for an operator mid-incident.
    eng = state["engine"]
    assert eng["queue_depth"] == 0
    assert eng["free_pages"] == eng["allocatable_pages"]
    assert eng["config"]["max_slots"] == 3
    assert state["span_capacity"] >= len(state["spans"])


def test_generated_trace_id_when_header_absent_or_hostile(served):
    """No header (or a hostile one) still yields a usable id, echoed
    everywhere the same way."""
    _, _, server = served
    out, headers = _post_raw(
        server.port, {"prompt": [9, 10], "max_new_tokens": 2}
    )
    assert out["trace_id"]
    assert headers.get("X-Request-Id") == out["trace_id"]
    int(out["trace_id"], 16)  # generated shape
    bad, _ = _post_raw(
        server.port,
        {"prompt": [9, 10], "max_new_tokens": 2},
        headers={"X-Request-Id": 'evil"id\\'},
    )
    assert bad["trace_id"] != 'evil"id\\'


def test_stream_events_carry_trace_id(served):
    """Every SSE event — per-token and done — carries the request's
    trace id so a client can correlate a stream with server telemetry."""
    cfg, params, server = served
    events = _post_stream(
        server.port, {"prompt": [3, 141, 59], "max_new_tokens": 4}
    )
    tids = {e.get("trace_id") for e in events}
    assert len(tids) == 1 and tids != {None}


def test_serving_metrics_cover_latency_and_pool(served):
    """/metrics carries the canonical serving set with observations:
    nonzero TTFT and ITL histogram counts, queue-depth and
    KV-page-utilization gauges (the request traffic of this module's
    earlier tests has already flowed through the shared registry)."""
    import re

    _, _, server = served
    # Ensure at least one multi-token request contributed ITL samples.
    _post(server.port, {"prompt": [5, 6, 7], "max_new_tokens": 4})
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=30
    ) as r:
        text = r.read().decode()

    def series(name):
        m = re.search(rf"^{name} (\S+)$", text, re.M)
        assert m, f"{name} missing from exposition"
        return float(m.group(1))

    assert series("tpu_engine_ttft_seconds_count") > 0
    assert series("tpu_engine_itl_seconds_count") > 0
    assert series("tpu_engine_queued_requests") == 0
    assert series("tpu_engine_free_pages") > 0
    assert series("tpu_engine_kv_page_utilization") == 0.0
    for name in (
        "tpu_engine_ttft_seconds",
        "tpu_engine_itl_seconds",
        "tpu_engine_kv_page_utilization",
        "tpu_engine_spec_rejected_total",
    ):
        assert f"# HELP {name} " in text
        assert f"# TYPE {name} " in text


def test_decode_block_cli_resolution():
    """Round-5 data-chosen serving default: an unset --decode-block
    resolves to 16, drops to 1 when --spec-gamma is set (the engine
    rejects blocks+speculation), and an explicit value always wins."""
    from k8s_device_plugin_tpu.models.http_server import _resolve_decode_block

    assert _resolve_decode_block(None, 0) == 16
    assert _resolve_decode_block(None, 2) == 1
    assert _resolve_decode_block(8, 0) == 8
    # Explicit block + speculation is passed through for the ENGINE to
    # reject — resolution must not silently override an operator choice.
    assert _resolve_decode_block(8, 2) == 8
    assert _resolve_decode_block(1, 0) == 1


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        assert r.status == 200
        return json.loads(r.read())


def test_debug_endpoints_smoke(served):
    """Endpoint-rot guard: every GET /debug/* answers 200 with parseable
    JSON of the documented shape — state, profile (per-phase step
    breakdown), incidents, flight."""
    _, _, server = served
    # Ensure the profiler has steps regardless of test ordering.
    _post(server.port, {"prompt": [5, 6, 7], "max_new_tokens": 3})
    state = _get(server.port, "/debug/state")
    assert "engine" in state and state["loop_alive"]
    prof = _get(server.port, "/debug/profile")
    assert prof["steps"] > 0 and prof["window"] > 0
    # The seven step phases keep their names (chipbench reads their
    # total_s); idle and the finer phases are further keys.
    assert set(prof["phases"]) >= {
        "schedule", "prefill", "dispatch", "readback", "sample",
        "host_gap", "spec_verify", "idle", "prefill.graft",
    }
    assert all("total_s" in v for v in prof["phases"].values())
    # Real decode happened, so the dispatch/readback phases have samples
    # and the step percentiles are populated; the overlap window counts
    # are served alongside.
    assert prof["phases"]["dispatch"]["window_steps"] > 0
    assert prof["phases"]["readback"]["window_steps"] > 0
    assert {"window_hits", "window_discards", "hit_ratio"} <= set(
        prof["overlap"]
    )
    assert prof["step_ms"]["p99"] >= prof["step_ms"]["p50"] > 0
    assert prof["occupancy"]["mean_kv_page_utilization"] >= 0.0
    # A prefill job's zero cache is one dispatch of its compiled maker.
    made = prof["prefill_cache"]
    assert made["jobs"] == made["dispatches"] >= made["programs"] >= 1
    inc = _get(server.port, "/debug/incidents")
    assert "incidents" in inc and "detectors" in inc
    fl = _get(server.port, "/debug/flight")
    assert fl["name"] == "engine"
    assert isinstance(fl["events"], list) and "dropped_by_kind" in fl
    # KV tiering snapshot (models/engine_kvcache.py): present and shaped
    # whether or not the tiers are enabled (this engine runs the library
    # default, retention off) — operators read the same keys either way.
    kv = _get(server.port, "/debug/kvcache")
    assert {"retain", "retained_pages", "host", "hits", "restores",
            "reclaims", "offloads", "resumes"} <= set(kv)
    assert {"retained", "host"} <= set(kv["hits"])
    assert {"restored", "recompute"} <= set(kv["resumes"])
    assert kv["host"]["bytes"] <= kv["host"]["budget_bytes"] or not kv[
        "host"
    ]["enabled"]
    # The engine snapshot carries the same block (debug_state parity).
    assert state["engine"]["kvcache"]["retain"] == kv["retain"]


def test_forced_incident_at_debug_incidents(served):
    """Acceptance path: an injected slow step yields an incident record
    at /debug/incidents containing the surrounding flight window."""
    _, _, server = served
    eng = server.engine
    eng.flight.record("engine.step", steps=eng.profiler.steps)
    mon = eng.anomaly
    # Flood the baseline so earlier real steps (compiles included) wash
    # out, then sustain a 400x deviation past the engine-configured
    # gate (warmup 50, sustain 3).
    for _ in range(200):
        mon.observe("engine.step_seconds", 0.005)
    for _ in range(4):
        mon.observe("engine.step_seconds", 2.0)
    data = _get(server.port, "/debug/incidents")
    assert data["incidents_total"] >= 1
    last = data["incidents"][-1]
    assert last["metric"] == "engine.step_seconds"
    assert last["observed"] == 2.0
    assert last["baseline_mean"] < 0.1
    assert last["z"] > 6.0
    kinds = [e["kind"] for e in last["flight_window"]]
    assert "engine.step" in kinds


def test_sigusr2_dumps_live_engine_flight(served, tmp_path):
    """Acceptance path: with the serving engine running, `kill -USR2`
    produces a JSON flight dump (events + drop accounting) on disk."""
    import os
    import signal

    from k8s_device_plugin_tpu.utils import flight as flight_mod

    if not hasattr(signal, "SIGUSR2"):
        pytest.skip("platform without SIGUSR2")
    _, _, server = served
    box = server.engine.flight
    box.record("engine.step", marker="sigusr2-test")
    flight_mod.register(box)
    handle = flight_mod.install_dump_handlers(str(tmp_path))
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 5.0
        dumps = []
        while time.time() < deadline and not dumps:
            dumps = [p for p in os.listdir(tmp_path) if "sigusr2" in p]
            time.sleep(0.01)
        assert dumps, "SIGUSR2 produced no dump with the engine running"
        with open(tmp_path / dumps[0]) as f:
            payload = json.load(f)
        rec = payload["recorders"]["engine"]
        assert any(e.get("marker") == "sigusr2-test" for e in rec["events"])
        assert "dropped" in rec and "dropped_by_kind" in rec
    finally:
        handle.uninstall()
        flight_mod.unregister(box)


def test_profile_capture_spans_live_steps(served):
    """POST /debug/profile/capture grabs a jax.profiler trace spanning
    the next engine step(s) of a LIVE serving loop."""
    import os

    _, _, server = served
    # Retry the capture with a fresh background request if a scheduling
    # hiccup lets the generate drain before the capture loop arms (the
    # CI box is small; the 409-free path is what matters here).
    for _ in range(3):
        bg = threading.Thread(
            target=lambda: _post(
                server.port, {"prompt": [9, 8, 7], "max_new_tokens": 24}
            ),
            daemon=True,
        )
        bg.start()
        out = _post_path(
            server.port, "/debug/profile/capture", {"steps": 1, "timeout_s": 20}
        )
        bg.join(timeout=60)
        if out["steps_captured"] >= 1:
            break
    assert out["steps_requested"] == 1
    assert out["steps_captured"] >= 1
    found = [
        os.path.join(r, f) for r, _, fs in os.walk(out["trace_dir"]) for f in fs
    ]
    assert found, "profiler wrote nothing into the capture dir"
    # Malformed bodies answer 400.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post_path(server.port, "/debug/profile/capture", {"steps": 0})
    assert e.value.code == 400


def test_graceful_drain_finishes_inflight_blocks_admission(shared_engine):
    """SIGTERM-path drain (EngineServer.begin_drain): admission stops
    (503 + Retry-After, /healthz -> draining) while the in-flight
    request keeps decoding to completion inside the grace window, then
    the loop stops and `drained` fires — a pod delete no longer cuts
    streams mid-token.  Rides the session engine (no new compiles; the
    in-flight request is slowed with an engine.readback delay failpoint
    so the drain demonstrably overlaps live decoding)."""
    from k8s_device_plugin_tpu.models.http_server import EngineServer
    from k8s_device_plugin_tpu.utils import failpoints

    _, _, eng = shared_engine
    # The session engine normally steps on the pytest main thread; hand
    # step ownership to this server's loop thread (the racecheck
    # OwnerGuard re-binds to whoever touches first — after the loop
    # thread dies at drain end, the main thread inherits back).
    if eng._inflight_guard is not None:
        eng._inflight_guard._owner = None
    server = EngineServer(eng, host="127.0.0.1", port=0).start()
    try:
        # ~24 decode steps x 10ms injected readback delay: the request
        # is mid-decode for ~250ms — ample room to drain around it.
        failpoints.arm("engine.readback", "delay", arg="0.01", count=24)
        results: dict = {}

        def _client():
            try:
                results["resp"] = _post(
                    server.port, {"prompt": [3, 141, 59], "max_new_tokens": 24}
                )
            except Exception as e:  # surfaced by the asserts below
                results["err"] = e

        client = threading.Thread(target=_client, daemon=True)
        client.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not (
            eng.queue or any(s is not None for s in eng.slots)
        ):
            time.sleep(0.002)
        assert eng.queue or any(s is not None for s in eng.slots)
        server.begin_drain(grace_s=30.0)
        server.begin_drain(grace_s=30.0)  # idempotent
        # Admission is closed the moment draining starts...
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": [9], "max_new_tokens": 2})
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") is not None
        # ...and readiness reads draining.
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz", timeout=5
            )
        assert e.value.code == 503
        assert json.loads(e.value.read())["status"] == "draining"
        # The in-flight request still finishes, full length, no cut.
        assert server.drained.wait(30), "drain never completed"
        client.join(timeout=10)
        assert "err" not in results, results.get("err")
        assert len(results["resp"]["tokens"]) == 24
        events = {e["kind"]: e for e in eng.flight.window(
            kinds=["server.drain_begin", "server.drain_end"]
        )}
        assert events["server.drain_begin"]["grace_s"] == 30.0
        assert events["server.drain_end"]["completed"] is True
        assert events["server.drain_end"]["cut_requests"] == 0
        # Engine drained whole: every slot and page back in the pool.
        assert all(s is None for s in eng.slots) and not eng.queue
        assert len(eng.free_pages) == eng.paged.num_pages - 1
    finally:
        failpoints.disarm_all()
        server.stop()


def test_metrics_lint_clean_on_live_engine_server(served):
    """The serving /metrics (engine + shared-registry series after a
    full suite of traffic) passes the strict exposition linter
    (tools/metrics_lint.py) scraped from the LIVE EngineServer."""
    import importlib.util
    import os as _os

    _, _, server = served
    _post(server.port, {"prompt": [5, 4, 3], "max_new_tokens": 3})
    repo_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", _os.path.join(repo_root, "tools", "metrics_lint.py")
    )
    metrics_lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metrics_lint)
    errors = metrics_lint.lint_url(f"http://127.0.0.1:{server.port}/metrics")
    assert errors == [], errors


def test_debug_state_summary_mode(served):
    """/debug/state grew the router-poll surface: top-level queue_depth/
    active_slots/draining/fenced ride the full snapshot, and ?summary=1
    returns ONLY those scalars — no engine-lock snapshot, no span ring —
    so a K-replica poll fan-in costs the fleet ~nothing."""
    _, _, server = served
    full = _get_json(server.port, "/debug/state")
    assert full["queue_depth"] == 0
    assert full["active_slots"] == 0
    assert full["draining"] is False
    assert full["fenced"] is False
    assert full["loop_alive"] is True
    assert "engine" in full and "spans" in full and "fence" in full
    summary = _get_json(server.port, "/debug/state?summary=1")
    # The host-side overload signals (ISSUE 14) ride along; their
    # values depend on traffic order within the module fixture, so the
    # shape is pinned here and the populated-after-traffic behaviour in
    # test_summary_carries_host_side_overload_signals.
    assert "queue_wait_ewma_s" in summary
    assert "drain_rate_rps" in summary
    summary.pop("queue_wait_ewma_s")
    summary.pop("drain_rate_rps")
    # Cumulative SLI counters (ISSUE 16) ride the summary too — compact
    # [good, total] pairs the router deltas into its fleet tracker.
    # Values depend on traffic order within the module fixture; the
    # shape is pinned here.
    slo = summary.pop("slo")
    assert set(slo) == {"objectives"}
    assert set(slo["objectives"]) == {"ttft", "itl_p99", "availability"}
    for pair in slo["objectives"].values():
        good, total = pair
        assert 0 <= good <= total
    # Canary-prober oracle key + staleness feed (ISSUE 17): the weights
    # fingerprint is stable (params never change in-process), and the
    # cumulative request counter depends on module traffic order — the
    # advancing behaviour is pinned in
    # test_summary_params_fingerprint_and_requests_total.
    fp = summary.pop("params_fingerprint")
    assert isinstance(fp, str) and fp
    assert isinstance(summary.pop("requests_total"), int)
    # Process age (ISSUE 19): the controller's replica-minutes ledger
    # input; value is wall-clock dependent, shape pinned here.
    assert summary.pop("uptime_s") >= 0.0
    # Incident cursor (postmortem archaeology): the cumulative
    # AnomalyMonitor count the router's fleet collector watches for
    # advances; the trigger behaviour is pinned in
    # test_summary_incidents_total_advances_on_incident.
    assert isinstance(summary.pop("incidents_total"), int)
    # Fleet-KV-fabric advertisement (router/fabric.py): a wire bloom
    # dict when this engine can serve any-peer pulls, else null; the
    # populated shape is pinned in test_engine_handoff.py.
    digest = summary.pop("fabric_digest")
    assert digest is None or set(digest) >= {"m", "k", "bits", "count"}
    assert summary == {
        "role": "unified",
        "queue_depth": 0,
        "active_slots": 0,
        "draining": False,
        "fenced": False,
        "loop_alive": True,
    }


def test_summary_incidents_total_advances_on_incident(served):
    """The postmortem trigger cursor: every AnomalyMonitor incident
    (detector-emitted or discrete report) advances the summary's
    cumulative incidents_total, which the router's fleet collector
    turns into a capture."""
    _, _, server = served
    before = _get_json(server.port, "/debug/state?summary=1")[
        "incidents_total"
    ]
    server.engine.anomaly.report(
        "engine.fenced", reason="summary-pin", source="operator"
    )
    after = _get_json(server.port, "/debug/state?summary=1")[
        "incidents_total"
    ]
    assert after == before + 1


def test_summary_params_fingerprint_and_requests_total(served):
    """The ?summary=1 canary contract (ISSUE 17): params_fingerprint is
    the real snapshot-format fingerprint of the engine's own weights,
    stable across polls; requests_total advances with every served
    request (the prober's staleness detector watches it freeze)."""
    from k8s_device_plugin_tpu.models import engine_snapshot as snap_mod

    _, params, server = served
    s1 = _get_json(server.port, "/debug/state?summary=1")
    assert s1["params_fingerprint"] == snap_mod.params_fingerprint(params)
    _post(server.port, {"prompt": [5, 6, 7], "max_new_tokens": 3})
    s2 = _get_json(server.port, "/debug/state?summary=1")
    assert s2["params_fingerprint"] == s1["params_fingerprint"]
    assert s2["requests_total"] == s1["requests_total"] + 1


def test_canary_prober_against_real_engine(served):
    """The shared-compile integration: the canary prober captures its
    oracle from the real engine's own first greedy response and every
    later probe matches bit-exactly — same warmed prompt bucket as the
    module's other traffic, zero new XLA compiles."""
    from k8s_device_plugin_tpu.router.prober import (
        CanaryConfig,
        CanaryProber,
    )

    _, _, server = served
    name = f"127.0.0.1:{server.port}"
    prober = CanaryProber(
        lambda: [name],
        config=CanaryConfig(
            interval_s=0.05,
            probe_tokens=3,
            prompts=((5, 6, 7),),  # the module's warmed bucket
            via_router=False,
        ),
    )
    assert prober.probe_once() == {name: "capture"}
    assert prober.probe_once() == {name: "match"}
    snap = prober.snapshot()
    [oracle] = snap["oracles"]
    # The oracle IS the engine's unary answer for the same prompt —
    # greedy decode is a pure function of (weights, prompt).
    unary = _post(server.port, {"prompt": [5, 6, 7], "max_new_tokens": 3})
    assert oracle["tokens"] == unary["tokens"]
    row = snap["replicas"][name]
    assert row["mismatches"] == 0 and row["ttft_s"] is not None


def test_debug_slo_and_usage_endpoints(served):
    """GET /debug/slo + /debug/usage (ISSUE 16): the engine's own SLO
    tracker snapshot and the per-tenant usage meter, over the wire."""
    _, _, server = served
    out = _post(server.port, {
        "prompt": [5, 6, 7], "max_new_tokens": 3, "tenant": "slo-probe",
    })
    assert len(out["tokens"]) == 3
    slo = _get_json(server.port, "/debug/slo")
    assert slo["enabled"] is True
    avail = slo["objectives"]["availability"]
    assert avail["target"] == 0.999
    good, total = avail["totals"]
    assert total >= 1 and good >= 1
    assert set(avail["windows"]) == {"5m", "30m", "6h"}
    assert [r["name"] for r in slo["rules"]] == ["fast_burn", "slow_burn"]
    usage = _get_json(server.port, "/debug/usage")
    assert usage["enabled"] is True
    probe = usage["tenants"]["slo-probe"]
    assert probe["requests"] >= 1
    assert probe["prompt_tokens"] >= 3
    assert probe["decode_tokens"] >= 3
    assert probe["kv_page_seconds"] > 0.0
    # The tenant-labeled meters exported the same charge.
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=30
    ) as resp:
        metrics_text = resp.read().decode()
    assert 'tpu_engine_tenant_requests_total{tenant="slo-probe"}' in (
        metrics_text
    )
    assert 'tpu_engine_sli_events_total{objective="availability",' in (
        metrics_text
    )


# ======================================================================
# Overload control over HTTP (ISSUE 9): the deadline/priority/tenant
# contract, typed shed verdicts, Retry-After on every 503, the
# /debug/admission surface, and the timeout-cancel slot-release path.
# ======================================================================


def test_overload_headers_flow_and_queue_wait_metric(served):
    """X-Request-Priority/X-Tenant-Id/X-Request-Deadline are adopted
    (response still the oracle tokens), the queue-wait histogram gains
    a priority-labeled observation, and /debug/admission reports the
    tenant's admission."""
    cfg, params, server = served
    prompt, n = [11, 12, 13], 4
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": n}).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Request-Priority": "high",
            "X-Tenant-Id": "acme",
            "X-Request-Deadline": "60",
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        body = json.loads(resp.read())
    assert body["tokens"] == _oracle(cfg, params, prompt, n)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=30
    ).read().decode()
    assert 'tpu_engine_queue_wait_seconds_bucket{priority="high"' in text
    assert "tpu_engine_goodput_tokens_total" in text
    adm = _get_json(server.port, "/debug/admission")
    assert adm["enabled"] is True
    assert adm["tenants"]["acme"]["admitted"] >= 1
    # The queue span carries the limiter's per-request input signal.
    state = _get_json(server.port, "/debug/state")
    queue_spans = [s for s in state["spans"] if s["name"] == "queue"]
    assert queue_spans and all(
        "wait_s" in s["attrs"] for s in queue_spans
    )


def test_expired_deadline_fails_fast_504(served):
    """A spent X-Request-Deadline answers 504 WITHOUT enqueueing (queue
    depth untouched) — the fail-fast half of the deadline contract."""
    _, _, server = served
    depth0 = _get_json(server.port, "/debug/state?summary=1")["queue_depth"]
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generate",
        data=json.dumps({"prompt": [1, 2], "max_new_tokens": 4}).encode(),
        headers={"X-Request-Deadline": "0"},
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 504
    assert json.loads(e.value.read())["shed"] == "expired"
    assert (
        _get_json(server.port, "/debug/state?summary=1")["queue_depth"]
        == depth0
    )


def test_every_engine_503_carries_retry_after(served):
    """The 503 contract (drain AND overload shed): Retry-After on every
    one, X-Shed marking load sheds so a router backs off without
    ejecting the replica.  (The router-side floor is pinned in
    tests/test_router.py — together they are the end-to-end pin.)"""
    _, _, server = served
    # Drain 503.
    server._draining.set()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": [1], "max_new_tokens": 2})
        assert e.value.code == 503
        assert float(e.value.headers["Retry-After"]) >= 1.0
        assert e.value.headers.get("X-Shed") is None  # drain, not shed
        # /healthz during drain is a 503 with Retry-After too.
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz", timeout=5
            )
        assert e.value.code == 503
        assert float(e.value.headers["Retry-After"]) >= 1.0
    finally:
        server._draining.clear()
    # Submit-side overload shed 503 (queue cap forced to zero).
    ctl = server.engine.overload
    old_max = ctl.cfg.max_queue
    ctl.cfg.max_queue = 0
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": [1, 2], "max_new_tokens": 2})
        assert e.value.code == 503
        body = json.loads(e.value.read())
        assert body["shed"] == "queue_full"
        assert float(e.value.headers["Retry-After"]) >= 1.0
        assert e.value.headers["X-Shed"] == "queue_full"
    finally:
        ctl.cfg.max_queue = old_max


def test_request_timeout_cancels_and_frees_slot(shared_engine):
    """The wait-path bugfix pin: a unary request that outlives the
    server's request timeout answers 504 AND is cancelled in the
    engine — its slot and pages free immediately (asserted via the
    /debug/state queue_depth/active_slots surface), instead of decoding
    for a client that already gave up."""
    from k8s_device_plugin_tpu.models.http_server import EngineServer
    from k8s_device_plugin_tpu.utils import failpoints

    _, _, eng = shared_engine
    if eng._inflight_guard is not None:
        eng._inflight_guard._owner = None  # loop thread takes ownership
    server = EngineServer(
        eng, host="127.0.0.1", port=0, request_timeout_s=0.2
    ).start()
    try:
        # ~20ms of injected readback delay per step: the 25-token decode
        # takes ~500ms, comfortably past the 0.2s request timeout.
        failpoints.arm("engine.readback", "delay", arg="0.02", count=40)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": [3, 141, 59], "max_new_tokens": 25},
                  timeout=30)
        assert e.value.code == 504
        # The cancel must release the slot/pages promptly: poll the
        # same summary surface a router polls.
        deadline = time.monotonic() + 5
        summary = None
        while time.monotonic() < deadline:
            summary = _get_json(server.port, "/debug/state?summary=1")
            if summary["queue_depth"] == 0 and summary["active_slots"] == 0:
                break
            time.sleep(0.02)
        assert summary["queue_depth"] == 0, summary
        assert summary["active_slots"] == 0, summary
        assert len(eng.free_pages) == eng.paged.num_pages - 1
    finally:
        failpoints.disarm_all()
        server.stop()
        if eng._inflight_guard is not None:
            eng._inflight_guard._owner = None  # hand back to pytest thread


# --------------------------------------------------------- replica fencing


def test_fence_endpoints_healthz_summary_and_admission(served):
    """Operator-forced fencing (POST /debug/fence — the rollout lever,
    same code path as the watchdog): /healthz flips to fenced, the
    router's summary poll grows ``fenced``, admission answers a plain
    503 + Retry-After (no X-Shed: take me out of rotation), and
    /debug/state carries the fence block.  Unfence restores all of it."""
    cfg, params, server = served
    try:
        out = _post_path(server.port, "/debug/fence", {"reason": "rollout"})
        assert out == {"fenced": True, "reason": "rollout", "changed": True}
        # Idempotent: a second fence reports unchanged.
        out = _post_path(server.port, "/debug/fence", {})
        assert out["fenced"] and not out["changed"]
        with pytest.raises(urllib.error.HTTPError) as e:
            _get_json(server.port, "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read())["status"] == "fenced"
        summary = _get_json(server.port, "/debug/state?summary=1")
        assert summary["fenced"] is True
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": [3, 141, 59], "max_new_tokens": 6})
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After")
        assert e.value.headers.get("X-Shed") is None, (
            "a fence is not an overload shed: the router must demote, "
            "not merely back off"
        )
        state = _get_json(server.port, "/debug/state")
        fence = state["fence"]
        assert fence["fenced"] and fence["reason"] == "rollout"
        assert fence["source"] == "operator" and fence["fences_total"] >= 1
        # The fence is an incident and a flight event, not just a flag.
        events = server.engine.flight.window(kinds=["engine.fenced"])
        assert events and events[-1]["reason"] == "rollout"
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30
        ).read().decode()
        assert "tpu_engine_fenced 1" in body
        assert 'tpu_engine_fences_total{source="operator"}' in body
    finally:
        out = _post_path(server.port, "/debug/unfence", {})
    assert out == {"fenced": False, "changed": True}
    assert _get_json(server.port, "/healthz")["status"] == "ok"
    assert _get_json(server.port, "/debug/state?summary=1")["fenced"] is False
    # Same prompt/length as test_generate_matches_oracle: the oracle
    # program is already compiled — serving-resumed proof at zero cost.
    prompt = [3, 141, 59]
    got = _post(server.port, {"prompt": prompt, "max_new_tokens": 6})
    assert got["tokens"] == _oracle(cfg, params, prompt, 6)


def test_watchdog_fence_cuts_stream_no_done_event(shared_engine):
    """The hung-step fence end to end on a live server: a readback hang
    (the `engine.readback` hang failpoint — the wedged-DMA shape) trips
    the watchdog, the replica fences, and the in-flight SSE stream is
    CUT with no done/error event (the shape the router's zero-drop
    failover resubmits).  Unfence re-arms: the replica serves again.

    The same contract is scored with measured precision/recall by the
    readback-hang chaos scenario."""
    from k8s_device_plugin_tpu.models.engine_watchdog import StepWatchdog
    from k8s_device_plugin_tpu.utils import failpoints

    cfg, params, eng = shared_engine
    if eng._inflight_guard is not None:
        eng._inflight_guard._owner = None  # loop thread takes ownership
    wd = StepWatchdog(
        lambda info: None,  # EngineServer binds the fence path
        min_deadline_s=0.3,
        grace_deadline_s=20.0,
        warmup=2,
        poll_interval_s=0.05,
    )
    server = EngineServer(
        eng, host="127.0.0.1", port=0, watchdog=wd, request_timeout_s=30
    ).start()
    lines: list[dict] = []
    stream_done = threading.Event()

    def _stream():
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generate",
            data=json.dumps(
                {"prompt": [3, 141, 59], "max_new_tokens": 20,
                 "stream": True}
            ).encode(),
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                for line in resp:
                    line = line.strip()
                    if line.startswith(b"data:"):
                        lines.append(json.loads(line[5:]))
        except OSError:
            pass
        finally:
            stream_done.set()

    try:
        # Baseline: two quick unary requests past the watchdog warmup.
        for _ in range(2):
            _post(server.port, {"prompt": [3, 141, 59], "max_new_tokens": 3})
        t = threading.Thread(target=_stream, daemon=True)
        t.start()
        # Let the stream reach steady decode (past the activation grace
        # step), THEN wedge the readback: the hang lands on a
        # tight-deadline step.
        deadline = time.monotonic() + 10
        while len(lines) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(lines) >= 2, "stream never started"
        failpoints.arm("engine.readback", "hang", arg="10")
        fence_deadline = time.monotonic() + 8
        fenced = False
        while time.monotonic() < fence_deadline:
            if _get_json(server.port, "/debug/state?summary=1")["fenced"]:
                fenced = True
                break
            time.sleep(0.05)
        assert fenced, "watchdog never fenced the hung step"
        assert stream_done.wait(5), "fence did not cut the stream"
        assert not any("done" in e or "error" in e for e in lines), (
            "a fenced stream must be CUT, not completed: the router's "
            "failover keys off the broken stream"
        )
        trip = wd.snapshot()["last_trip"]
        assert trip and trip["kind"] == "hung_step"
        failpoints.disarm_all()  # release the hung step
        # Unfence: detectors re-arm, serving resumes.
        out = _post_path(server.port, "/debug/unfence", {})
        assert out["changed"]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                if _get_json(server.port, "/healthz")["status"] == "ok":
                    break
            except urllib.error.HTTPError:
                pass
            time.sleep(0.05)
        got = _post(
            server.port, {"prompt": [3, 141, 59], "max_new_tokens": 3},
            timeout=30,
        )
        assert len(got["tokens"]) == 3
        assert not wd.tripped, "unfence must re-arm the watchdog"
    finally:
        failpoints.disarm_all()
        eng.watchdog = None
        server.stop()
        if eng._inflight_guard is not None:
            eng._inflight_guard._owner = None  # hand back to pytest thread


# ======================================================================
# Hop-context adoption + /debug/spans (fleet tracing, ISSUE 12)
# ======================================================================


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as resp:
        return json.loads(resp.read())


def test_trace_context_header_adopted_and_tree_rooted(served):
    """A router-stamped X-Trace-Context wins over X-Request-Id: its
    trace id rides the response, and the request root span records the
    parent/hop/attempt attrs the fleet assembler joins on."""
    from k8s_device_plugin_tpu.utils.spans import (
        format_span_id,
        format_trace_context,
    )

    cfg, params, server = served
    header = format_trace_context("ctx-adopt-1", 42, 1, 2)
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generate",
        data=json.dumps({"prompt": [3, 141, 59], "max_new_tokens": 5}).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Request-Id": "should-lose",
            "X-Trace-Context": header,
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        got = json.loads(resp.read())
        assert resp.headers["X-Request-Id"] == "ctx-adopt-1"
    assert got["trace_id"] == "ctx-adopt-1"
    assert got["tokens"] == _oracle(cfg, params, [3, 141, 59], 5)
    spans = _get_json(server.port, "/debug/spans?rid=ctx-adopt-1")["spans"]
    root = next(s for s in spans if s["name"] == "request")
    assert root["attrs"]["parent"] == format_span_id(42)
    assert root["attrs"]["hop"] == 1
    assert root["attrs"]["attempt"] == 2
    # The ordinary per-request children still parent on the root.
    children = {
        s["name"] for s in spans if s.get("parent_id") == root["span_id"]
    }
    assert {"queue", "prefill", "decode"} <= children


def test_malformed_trace_context_falls_back_to_request_id(served):
    _, _, server = served
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/generate",
        data=json.dumps({"prompt": [3, 141, 59], "max_new_tokens": 2}).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Request-Id": "fallback-7",
            "X-Trace-Context": "not-a-context",
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        got = json.loads(resp.read())
    assert got["trace_id"] == "fallback-7"
    spans = _get_json(server.port, "/debug/spans?rid=fallback-7")["spans"]
    root = next(s for s in spans if s["name"] == "request")
    # No upstream context: no cross-process link attrs.
    assert "parent" not in root["attrs"]


def test_debug_spans_endpoint_shape_and_rid_filter(served):
    _, _, server = served
    _post(server.port, {"prompt": [3, 141, 59], "max_new_tokens": 2})
    full = _get_json(server.port, "/debug/spans")
    assert set(full) == {"name", "spans", "dropped", "capacity"}
    assert full["spans"], "ring should not be empty after traffic"
    tids = {s["trace_id"] for s in full["spans"]}
    assert len(tids) > 1, "expect several traces in the module fixture ring"
    some = next(iter(tids - {"engine"}))
    only = _get_json(server.port, f"/debug/spans?rid={some}")
    assert only["spans"] and {s["trace_id"] for s in only["spans"]} == {some}


def test_summary_carries_host_side_overload_signals(served):
    """The router's poll surface grew the migration/scale signals
    (ISSUE 14): ?summary=1 carries queue_wait_ewma_s / drain_rate_rps
    off the overload controller — populated after traffic on this
    overload-on fixture, and still present (as null) in the full
    state's top level."""
    _, _, server = served
    _post(server.port, {"prompt": [9, 8, 7], "max_new_tokens": 2})
    summary = _get(server.port, "/debug/state?summary=1")
    assert "queue_wait_ewma_s" in summary and "drain_rate_rps" in summary
    assert summary["queue_wait_ewma_s"] is not None, (
        "overload-on fixture served traffic: the wait EWMA must exist"
    )
    full = _get(server.port, "/debug/state")
    assert "queue_wait_ewma_s" in full


def test_debug_snapshot_endpoint_contract_smoke(served):
    """GET /debug/snapshot on a live server: 200 + negotiation headers
    + a parseable wire stream (arena-less fixture: zero entries), 409
    on a mismatched fingerprint BEFORE any bytes, 416 on Range.  The
    warm-path byte-for-byte semantics ride the tiered engine suite in
    tests/test_engine_snapshot.py."""
    import http.client
    import io

    from k8s_device_plugin_tpu.models import engine_snapshot as snap

    _, _, server = served

    def _raw(headers):
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        conn.request("GET", "/debug/snapshot", headers=headers)
        resp = conn.getresponse()
        out = (resp.status, dict(resp.getheaders()), resp.read())
        conn.close()
        return out

    status, headers, body = _raw({})
    assert status == 200
    assert snap.LAYOUT_HEADER in headers and snap.PARAMS_HEADER in headers
    with server.engine._lock:
        layout = snap.snapshot_layout(server.engine)
    _, entries = snap._parse_snapshot(
        io.BytesIO(body), layout, headers[snap.PARAMS_HEADER]
    )
    assert len(entries) == int(headers[snap.ENTRIES_HEADER])
    status, _, _ = _raw({snap.PARAMS_HEADER: "deadbeef"})
    assert status == 409
    status, _, _ = _raw({"Range": "bytes=0-99"})
    assert status == 416
