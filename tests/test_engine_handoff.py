"""Disaggregated prefill/decode handoff (models/engine_handoff.py).

The bar: a decode-role replica serving a handed-off prefix must emit
BIT-IDENTICAL tokens to a local-prefill oracle while SKIPPING the
prefill compute the transferred pages cover; every failure (torn
stream, dead source, refusal) degrades to ordinary local prefill.

Budget discipline: every engine test rides the session-scoped
``shared_engine`` fixture with the kvcache suite's knob pattern (flip
retention/arena/role on, restore after) — the role flags and the
handoff machinery are host-side state over the SAME compiled programs,
so the suite adds no model compiles (the chunked-prefill program and
the tiny seed/readback scatters are the only fresh shapes).
"""

import io
import json
import threading
import urllib.error
import urllib.request

import pytest

from k8s_device_plugin_tpu.models import engine_handoff as handoff
from k8s_device_plugin_tpu.models import engine_snapshot as snap
from k8s_device_plugin_tpu.utils import failpoints


@pytest.fixture()
def tiered_engine(shared_engine):
    """The kvcache suite's knob discipline, handoff flavor: tiers on,
    role restored to unified afterwards, pool exact at exit."""
    cfg, params, eng = shared_engine
    eng._kv_retain = True
    eng._kv_arena.budget_bytes = 8 << 20
    try:
        yield cfg, params, eng
    finally:
        eng.role = "unified"
        eng._handoff_skip_covered = False
        eng._prefill_chunk = None
        eng._kv_retain = False
        eng.kvcache_clear()
        eng._kv_arena.budget_bytes = 0
        assert len(eng.free_pages) == eng.paged.num_pages - 1


def _wait(predicate, timeout=5.0):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def _idle(eng):
    """Wait for the served engine's loop to finish every teardown (a
    probe's page release runs on the loop thread AFTER the stream's
    last entry reaches the client — clearing tiers before it lands
    would leave its pages retained past the clear)."""
    assert _wait(
        lambda: all(s is None for s in eng.slots)
        and not eng._pending
        and not eng.queue
    ), "engine never went idle"


def _drain(eng, tap, collect=True):
    """Step the engine until the tap's probe finished; return the
    entries in push order."""
    entries = []
    for _ in range(200):
        eng.step()
        if collect:
            while True:
                e = tap.pop(0.0)
                if e is None:
                    break
                entries.append(e)
        if tap.req.done and (not collect or tap.pushed <= len(entries)):
            break
    return entries


# ------------------------------------------------------------ wire format


def test_wire_format_is_the_snapshot_format():
    """encode_preamble + encode_entry concatenated must be byte-for-byte
    what encode_snapshot streams (same header modulo its timestamp, same
    entry records) — the handoff stream parses through the SAME
    verifier, so the formats must be provably one."""
    import numpy as np

    layout = {
        "page_size": 4,
        "layers": {"l0": {"pool_key": {"shape": [2], "dtype": "float32"}}},
    }
    entries = {
        ("prefix", -1, (1, 2, 3, 4)): {
            "l0": {"pool_key": np.asarray([1.5, -2.0], np.float32)}
        }
    }
    whole = b"".join(snap.encode_snapshot(layout, "fp", entries))
    split = snap.encode_preamble(layout, "fp", 1) + snap.encode_entry(
        layout, ("prefix", -1, (1, 2, 3, 4)), entries[("prefix", -1, (1, 2, 3, 4))]
    )
    # Headers differ only in created_unix; entries must be identical and
    # BOTH streams must parse to the same rows through the one verifier.
    for wire in (whole, split):
        header, parsed = snap._parse_snapshot(io.BytesIO(wire), layout, "fp")
        assert header["entries"] == 1
        assert parsed[0][0] == ("prefix", -1, (1, 2, 3, 4))
        assert parsed[0][1]["l0"]["pool_key"].tolist() == [1.5, -2.0]


def test_role_validation(shared_engine):
    """Split roles refuse an engine without the KV tiers they live on
    (ctor contract — a silently recomputing prefill replica is worse
    than a loud refusal).  Ctor-only: nothing steps, nothing compiles."""
    from k8s_device_plugin_tpu.models.engine import ServingEngine

    cfg, params, eng = shared_engine
    paged = eng.paged
    with pytest.raises(ValueError, match="role must be one of"):
        ServingEngine(cfg, params, paged, role="bogus")
    with pytest.raises(ValueError, match="kv_retain"):
        ServingEngine(cfg, params, paged, role="prefill")
    with pytest.raises(ValueError, match="kv_host_cache_mb"):
        ServingEngine(cfg, params, paged, role="decode", kv_retain=True)
    with pytest.raises(ValueError, match="prefix_sharing"):
        ServingEngine(
            cfg, params, paged, role="decode", kv_retain=True,
            kv_host_cache_mb=8, prefix_sharing=False,
        )


# --------------------------------------------------- prefill-role streaming


def test_prefill_probe_streams_entries_chunk_by_chunk(tiered_engine):
    """A chunked prefill probe pushes each FULL page's entry as its
    chunk completes — not after the whole prompt — publishes the same
    rows into the arena, and the entry bytes round-trip the snapshot
    verifier bit-identically against the device pages."""
    cfg, params, eng = tiered_engine
    eng.role = "prefill"
    eng._prefill_chunk = 4  # page_size 4: one page per chunk
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]  # 2 full pages, bucket 8
    tap = eng.handoff_begin(prompt, None)
    try:
        seen_incremental = False
        entries = []
        for _ in range(50):
            eng.step()
            while True:
                e = tap.pop(0.0)
                if e is None:
                    break
                entries.append(e)
            if entries and not tap.req.done:
                seen_incremental = True  # entry BEFORE the probe finished
            if tap.req.done and tap.pushed <= len(entries):
                break
    finally:
        eng.handoff_end(tap)
    assert [k for k, _ in entries] == [
        ("prefix", -1, tuple(prompt[:4])),
        ("prefix", -1, tuple(prompt)),
    ]
    assert seen_incremental, "entries must stream as chunks land"
    # Published: the arena holds both entries, content-addressed.
    for key, _ in entries:
        assert key in eng._kv_arena
    assert eng.handoff_published_entries >= 2
    # The streamed rows are the bytes the graft wrote: compare against
    # the registered device pages read back through the pool path.
    with eng._lock:
        resident = eng.handoff_resident_entries(prompt, None)
    assert resident is not None
    for (key, rows), (rkey, rrows) in zip(entries, resident):
        assert key == rkey
        for layer, pools in rows.items():
            for pool, arr in pools.items():
                assert arr.tobytes() == rrows[layer][pool].tobytes()
    assert any(
        e["kind"] == "handoff.published"
        for e in eng.flight.window(kinds=["handoff.published"])
    )


def test_handoff_coverage_walks_device_then_arena(tiered_engine):
    cfg, params, eng = tiered_engine
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    assert eng.handoff_coverage(prompt, None) == (0, 2)
    eng.run([(prompt, 4)])  # registers + retains both full pages
    assert eng.handoff_coverage(prompt, None) == (2, 2)
    with eng._lock:
        eng._kv_reclaim(len(eng._kv_retained))  # spill to the arena
    assert eng.handoff_coverage(prompt, None) == (2, 2)
    eng.kvcache_clear()
    assert eng.handoff_coverage(prompt, None) == (0, 2)


# ------------------------------------------- decode-role restore + skip


def test_decode_role_skips_covered_prefill_bit_identical(tiered_engine):
    """The acceptance pin: a decode-role engine admitting a handed-off
    prefix restores the pages, SKIPS the covered prefill chunks (the
    seeded dense cache stands in for them), and emits exactly the
    local-prefill oracle's tokens — greedy AND sampled."""
    cfg, params, eng = tiered_engine
    eng._prefill_chunk = 4
    import jax

    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    ref = eng.run([(prompt, 6)])[0].tokens  # local-prefill oracle

    def _reseed():
        # Sampled streams are a function of the key SCHEDULE: pin it to
        # the same point for the oracle and the handed-off run (the
        # restore path preserves the split count; engine history before
        # each run must too).
        eng._rng = eng._rep(jax.random.PRNGKey(42))
        eng._mark_state_dirty()

    _reseed()
    ref_sampled = eng.run(
        [(prompt, 6)], temperature=0.7, top_k=40
    )[0].tokens
    # The donor's wire bytes for this prompt, via the tiers.
    with eng._lock:
        eng._kv_reclaim(len(eng._kv_retained))
        layout = snap.snapshot_layout(eng)
        fp = snap.params_fingerprint(eng.params)
        resident = eng.handoff_resident_entries(prompt, None)
    wire = snap.encode_preamble(layout, fp, len(resident)) + b"".join(
        snap.encode_entry(layout, k, r) for k, r in resident
    )
    # The "joiner": every tier cleared, the wire re-admitted through the
    # one verifier, the engine flipped to the decode role.
    eng.kvcache_clear()
    _, parsed = snap._parse_snapshot(io.BytesIO(wire), layout, fp)
    assert snap._admit_entries(eng, parsed) == 2
    eng.role = "decode"
    eng._handoff_skip_covered = True
    skipped0, restores0 = eng.handoff_skipped_tokens, eng.kv_restores
    dispatches0 = eng.prefill_cache_dispatches
    got = eng.run([(prompt, 6)])[0].tokens
    assert got == ref, "handed-off decode must be bit-identical"
    assert eng.handoff_skipped_tokens > skipped0, "prefill was not skipped"
    # The seed wrote into the tree the compiled maker returned
    # (_zero_prefill_cache): one dispatch for the one job.
    assert eng.prefill_cache_dispatches == dispatches0 + 1
    assert eng.kv_restores > restores0, "pages were not restored"
    _reseed()
    got_sampled = eng.run([(prompt, 6)], temperature=0.7, top_k=40)[0].tokens
    assert got_sampled == ref_sampled, "sampled stream must match too"


def test_decode_role_local_prefill_fallback_unchanged(tiered_engine):
    """A decode-role engine admitting an UNCOVERED prompt (post-fetch-
    failure fallback) runs the ordinary full prefill — zero skip, exact
    oracle tokens."""
    cfg, params, eng = tiered_engine
    prompt = [9, 8, 7, 6, 5, 4, 3, 2]
    ref = eng.run([(prompt, 5)])[0].tokens
    eng.kvcache_clear()
    eng.role = "decode"
    eng._handoff_skip_covered = True
    skipped0 = eng.handoff_skipped_tokens
    got = eng.run([(prompt, 5)])[0].tokens
    assert got == ref
    assert eng.handoff_skipped_tokens == skipped0, "nothing to skip"


# ----------------------------------------------------- HTTP surfaces


def _served(eng, **kw):
    from k8s_device_plugin_tpu.models.http_server import EngineServer

    if eng._inflight_guard is not None:
        eng._inflight_guard._owner = None
    return EngineServer(eng, host="127.0.0.1", port=0, **kw).start()


def _post(port, path, payload, headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.getheaders()), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_v1_prefill_serves_wire_and_decode_gate_degrades(tiered_engine):
    """One served engine, both halves of the HTTP contract:

    - role=prefill: POST /v1/prefill streams a parse-clean wire body
      (fingerprint headers honored, 409 on mismatch), and /generate
      answers the typed 409.
    - role=decode: /generate without a locator answers 409 +
      X-Prefill-Needed; with an unreachable locator it degrades to
      LOCAL prefill and still answers the oracle tokens; /v1/prefill
      refuses; GET /debug/disagg reports it all.
    """
    cfg, params, eng = tiered_engine
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    ref = eng.run([(prompt, 5)])[0].tokens
    eng.kvcache_clear()
    eng.role = "prefill"
    server = _served(eng)
    try:
        with eng._lock:
            layout = snap.snapshot_layout(eng)
            fp = snap.params_fingerprint(eng.params)
        status, headers, wire = _post(
            server.port, "/v1/prefill", {"prompt": prompt},
            {snap.LAYOUT_HEADER: snap.layout_fingerprint(layout),
             snap.PARAMS_HEADER: fp},
        )
        assert status == 200
        assert headers[snap.ENTRIES_HEADER] == "2"
        buf = io.BytesIO(wire)
        _, entries = snap._parse_snapshot(buf, layout, fp)
        assert len(entries) == 2
        # The shipped logits ride the trailing section: the decode side
        # can admit this prompt with zero prefill compute.
        logits = handoff.read_logits_section(buf)
        assert logits is not None and logits.shape == (cfg.vocab_size,)
        # (serve accounting lands after the body: poll, don't race it)
        assert _wait(lambda: eng.handoff_serves == 1)
        assert eng.handoff_served_entries == 2
        # Fingerprint refusal before any bytes.
        status, _, _ = _post(
            server.port, "/v1/prefill", {"prompt": prompt},
            {snap.PARAMS_HEADER: "deadbeef"},
        )
        assert status == 409
        # The prefill role does not decode.
        status, _, body = _post(
            server.port, "/generate", {"prompt": prompt, "max_new_tokens": 2}
        )
        assert status == 409 and b"prefill" in body

        # ---- decode half (same server, role flipped; the wire above
        # is NOT re-admitted: the decode gate must refuse/degrade).
        _idle(eng)
        eng.kvcache_clear()
        eng.role = "decode"
        eng._handoff_skip_covered = True
        status, headers, body = _post(
            server.port, "/generate", {"prompt": prompt, "max_new_tokens": 5}
        )
        assert status == 409
        assert headers.get(handoff.PREFILL_NEEDED_HEADER) == "2"
        assert eng.handoff_refusals == 1
        # Unreachable locator: fetch fails, LOCAL prefill serves the
        # oracle tokens — zero new failure modes.
        status, _, body = _post(
            server.port, "/generate", {"prompt": prompt, "max_new_tokens": 5},
            {handoff.HANDOFF_SOURCE_HEADER: "127.0.0.1:1"},
        )
        assert status == 200
        assert json.loads(body)["tokens"] == ref
        assert eng.handoff_fetch_failures == 1
        fails = eng.flight.window(kinds=["handoff.fetch_failed"])
        assert fails and fails[-1]["outcome"] == "unreachable"
        # The LOCAL sentinel skips the fetch outright.
        status, _, body = _post(
            server.port, "/generate", {"prompt": prompt, "max_new_tokens": 5},
            {handoff.HANDOFF_SOURCE_HEADER: handoff.HANDOFF_LOCAL},
        )
        assert status == 200 and json.loads(body)["tokens"] == ref
        assert eng.handoff_fetch_failures == 1  # unchanged: no dial
        # Decode role serves RESIDENT prefixes to any peer (the fabric
        # any-peer pull path: the local prefills above made this prompt
        # resident) — and refuses a cold prompt WITHOUT probing (409 +
        # fabric.serve_refused; the arena stays untouched).
        status, _, _ = _post(server.port, "/v1/prefill", {"prompt": prompt})
        assert status == 200
        status, _, body = _post(
            server.port, "/v1/prefill", {"prompt": [5] * len(prompt)}
        )
        assert status == 409 and b"resident-only" in body
        refused = eng.flight.window(kinds=["fabric.serve_refused"])
        assert refused and refused[-1]["role"] == "decode"
        # /debug/disagg carries the ledger.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/disagg", timeout=10
        ) as resp:
            state = json.loads(resp.read())
        assert state["role"] == "decode"
        assert state["refusals"] == 1 and state["fetch_failures"] == 1
    finally:
        server.stop()


def test_handoff_serve_failpoints_tear_the_stream(tiered_engine):
    """Chaos seams: serve=error answers 503; serve=truncate tears the
    stream after a fraction of the entries so the decode-side parse
    raises (the prefill-died-mid-transfer shape the chaos scenario
    scores); fetch_prefill against the torn serve degrades clean."""
    cfg, params, eng = tiered_engine
    eng.role = "prefill"
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    eng.run([(prompt, 4)])  # make the prefix resident (no probe needed)
    server = _served(eng)
    try:
        with eng._lock:
            layout = snap.snapshot_layout(eng)
            fp = snap.params_fingerprint(eng.params)
        failpoints.arm("engine.handoff.serve", "error", count=1)
        status, _, _ = _post(server.port, "/v1/prefill", {"prompt": prompt})
        assert status == 503
        failpoints.arm("engine.handoff.serve", "truncate", arg="0.5",
                       count=1)
        status, headers, wire = _post(
            server.port, "/v1/prefill", {"prompt": prompt}
        )
        assert status == 200
        with pytest.raises(snap.SnapshotError):
            snap._parse_snapshot(io.BytesIO(wire), layout, fp)
        # The decode-side fetch of that torn stream: nothing admitted.
        failpoints.arm("engine.handoff.serve", "truncate", arg="0.5",
                       count=1)
        arena_before = len(eng._kv_arena)
        res = handoff.fetch_prefill(
            eng, f"127.0.0.1:{server.port}", prompt
        )
        assert not res["ok"] and res["outcome"] == "corrupt"
        assert len(eng._kv_arena) == arena_before, (
            "a torn transfer must admit nothing — and must NOT clear "
            "the serving arena"
        )
    finally:
        failpoints.disarm_all()
        server.stop()


def test_summary_and_debug_state_carry_role(tiered_engine):
    cfg, params, eng = tiered_engine
    eng.role = "decode"
    server = _served(eng)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/state?summary=1",
            timeout=10,
        ) as resp:
            assert json.loads(resp.read())["role"] == "decode"
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/state", timeout=10
        ) as resp:
            state = json.loads(resp.read())
        assert state["engine"]["config"]["role"] == "decode"
        assert state["engine"]["disagg"]["role"] == "decode"
    finally:
        server.stop()


# ------------------------------------------------------------ fleet fabric


def test_fabric_digest_advertises_resident_prefixes(tiered_engine):
    """The bloom advertisement covers exactly the cumulative full-page
    prefixes the replica can serve, roundtrips through the wire form,
    and is version-cached (the summary poll must not rebuild an
    unchanged filter).  ``None`` when the replica cannot serve pulls."""
    from k8s_device_plugin_tpu.utils.prefixbloom import PrefixBloom

    cfg, params, eng = tiered_engine
    eng.kvcache_clear()
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]  # 2 full pages @ page_size 4
    eng.run([(prompt, 3)])
    root = eng._trie_root(None)
    wire = eng.fabric_digest()
    assert wire is not None and wire["page_size"] == eng.paged.page_size
    assert wire["count"] >= 2
    bloom = PrefixBloom.from_wire(wire)
    assert bloom is not None
    assert bloom.contains(root, tuple(prompt[:4]))
    assert bloom.contains(root, tuple(prompt))
    # Version-keyed cache: an unchanged arena+trie returns the SAME
    # rendered dict with zero rebuild work.
    builds = eng.fabric_digest_builds
    assert eng.fabric_digest() is wire
    assert eng.fabric_digest_builds == builds
    # A replica that cannot serve pulls advertises nothing at all —
    # the locator must never place prefixes on it.
    eng.prefix_sharing = False
    try:
        assert eng.fabric_digest() is None
    finally:
        eng.prefix_sharing = True


def test_fabric_digest_invalidated_when_graft_unpends_pages(tiered_engine):
    """Regression: a digest built MID-prefill (the router poll racing a
    cold admission) sees only pending pages and caches an empty filter;
    the pending->grafted transition in ``_activate`` must invalidate
    that cache like any trie edit, or the replica advertises nothing
    until unrelated churn bumps a version.  Chunked prefill holds the
    pages pending across several steps so the race is deterministic."""
    cfg, params, eng = tiered_engine
    eng.kvcache_clear()
    eng._prefill_chunk = 4
    prompt = [3, 141, 59, 265, 35, 7, 7, 3, 1, 2, 9, 4]  # 3 full pages
    root = eng._trie_root(None)
    req = eng.submit(prompt, 2)
    eng.step()  # admit + first chunk: pages registered, still pending
    mid = eng.fabric_digest()
    assert mid is not None and mid["count"] == 0  # pending never advertised
    assert eng.fabric_digest() is mid  # ...and the empty filter is cached
    for _ in range(200):
        if req.done:
            break
        eng.step()
    assert req.done
    done = eng.fabric_digest()
    assert done is not mid, "graft did not invalidate the digest cache"
    assert done["count"] >= 3
    from k8s_device_plugin_tpu.utils.prefixbloom import PrefixBloom

    bloom = PrefixBloom.from_wire(done)
    for pages in (1, 2, 3):
        assert bloom.contains(root, tuple(prompt[: pages * 4]))


def test_fabric_partial_serve_stops_at_resident_coverage(tiered_engine):
    """Any-peer pull of a LONGER prompt sharing only the leading pages
    (the fleet-wide shared system prompt): a resident-only serve
    streams exactly the covered prefix — entry count in the preamble is
    the COVERED page count, every entry parses, and no probe ran."""
    cfg, params, eng = tiered_engine
    eng.kvcache_clear()
    shared = [3, 141, 59, 7, 11, 5, 9, 2]  # resident: 2 full pages
    eng.run([(shared, 3)])
    server = _served(eng)
    try:
        with eng._lock:
            layout = snap.snapshot_layout(eng)
            fp = snap.params_fingerprint(eng.params)
        probes_before = eng.handoff_serves
        published_before = eng.handoff_published_entries
        status, headers, wire = _post(
            server.port,
            "/v1/prefill",
            {"prompt": shared + [13, 2, 5, 8]},  # 3rd page NOT resident
            {handoff.FABRIC_RESIDENT_ONLY_HEADER: "1"},
        )
        assert status == 200
        assert headers[snap.ENTRIES_HEADER] == "2"
        _, entries = snap._parse_snapshot(io.BytesIO(wire), layout, fp)
        assert [e[0] for e in entries] == [
            ("prefix", eng._trie_root(None), tuple(shared[:4])),
            ("prefix", eng._trie_root(None), tuple(shared)),
        ]
        assert _wait(lambda: eng.handoff_serves == probes_before + 1)
        # No probe: the engine never admitted the longer prompt.
        assert eng.handoff_published_entries == published_before
    finally:
        server.stop()


def test_fabric_pull_and_drop_roundtrip_over_wire(tiered_engine):
    """``fabric_pull`` (the router's replication verb) admits the
    owner's pages into the host arena through the real /v1/prefill
    wire + parse-before-admit verifier; ``fabric_drop`` releases
    exactly those host copies while the trie-resident serving state
    stays untouched.  Self-pull keeps it to one engine — the wire
    path is identical either way."""
    cfg, params, eng = tiered_engine
    eng.kvcache_clear()
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    eng.run([(prompt, 3)])
    root = eng._trie_root(None)
    server = _served(eng)
    try:
        result = eng.fabric_pull(f"127.0.0.1:{server.port}", prompt)
        assert result["ok"] and result["restored"] == 2
        assert eng.fabric_pulls == 1
        assert ("prefix", root, tuple(prompt)) in eng._kv_arena
        pulled = eng.flight.window(kinds=["fabric.pulled"])
        assert pulled and pulled[-1]["restored"] == 2
        # Drop releases the HOST copies only...
        drop = eng.fabric_drop(prompt)
        assert drop == {"ok": True, "dropped": 2}
        assert eng.fabric_drops == 1
        assert ("prefix", root, tuple(prompt)) not in eng._kv_arena
        assert eng.flight.window(kinds=["fabric.dropped"])
        # ...so the replica is still an owner: resident-only serve of
        # the trie pages keeps answering.
        status, _, _ = _post(
            server.port,
            "/v1/prefill",
            {"prompt": prompt},
            {handoff.FABRIC_RESIDENT_ONLY_HEADER: "1"},
        )
        assert status == 200
        # The replica-side ledger carries all of it.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/fabric", timeout=10
        ) as resp:
            state = json.loads(resp.read())
        assert state["enabled"] and state["advertised_roots"] >= 2
        assert state["pulls"] == 1 and state["drops"] == 1
    finally:
        server.stop()
