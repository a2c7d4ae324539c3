"""Repo-specific contract configuration for the codelint passes.

This file IS the reviewed part of the analyzer: the lock-order
allowlist, the duck-typed receiver hints that make cross-object call
edges resolvable, and the catalog locations the drift pass reads.  A
new nested lock acquisition or a new documented catalog belongs HERE,
in review — never inferred silently by the passes.
"""

from __future__ import annotations

# Scan roots (repo-relative).  The passes analyze the shipped package;
# tests and tools lint themselves through their own suites.
SCAN_ROOTS = ["k8s_device_plugin_tpu"]

# ---------------------------------------------------------------- locks
#
# Duck-typed attribute -> (defining file, class).  `self.flight.record()`
# is untyped at the call site; these hints let the lock passes resolve
# the receiver so "holds engine lock -> takes flight lock" edges exist.
# Keep entries minimal and obvious; a wrong hint invents false edges.
ATTR_TYPES: dict = {
    "flight": ("k8s_device_plugin_tpu/utils/flight.py", "FlightRecorder"),
    "_flight": ("k8s_device_plugin_tpu/utils/flight.py", "FlightRecorder"),
    "breaker": ("k8s_device_plugin_tpu/router/breaker.py", "CircuitBreaker"),
    "budget": ("k8s_device_plugin_tpu/router/breaker.py", "RetryBudget"),
    "anomaly": ("k8s_device_plugin_tpu/utils/anomaly.py", "AnomalyMonitor"),
    "monitor": ("k8s_device_plugin_tpu/utils/anomaly.py", "AnomalyMonitor"),
}

# Allowlisted nested lock acquisitions, as (outer, inner) lock-identity
# pairs ("file:Class.attr").  Every entry is a reviewed ORDER: taking
# the inner while holding the outer is legal, the reverse is not (the
# lock-order pass flags both unlisted nestings and cycles).
#
# The repo-wide discipline these encode: leaf instruments (flight ring,
# metrics, anomaly baselines, breaker state) may be taken under a
# daemon's coarse lock; no leaf lock ever wraps a daemon lock back.
LOCK_ORDER_ALLOW: set = {
    # Engine lock -> leaf instruments (gauge updates + flight events
    # recorded while the step loop still holds the engine lock).
    (
        "k8s_device_plugin_tpu/models/engine.py:ServingEngine._lock",
        "k8s_device_plugin_tpu/utils/flight.py:FlightRecorder._lock",
    ),
    # Server admission condition -> engine lock (submit/cancel run under
    # the HTTP server's condition while calling into the engine).
    (
        "k8s_device_plugin_tpu/models/http_server.py:EngineServer._cond",
        "k8s_device_plugin_tpu/models/engine.py:ServingEngine._lock",
    ),
    # Router membership lock -> leaf instruments.
    (
        "k8s_device_plugin_tpu/router/server.py:RouterServer._lock",
        "k8s_device_plugin_tpu/utils/flight.py:FlightRecorder._lock",
    ),
    (
        "k8s_device_plugin_tpu/router/server.py:RouterServer._lock",
        "k8s_device_plugin_tpu/router/breaker.py:CircuitBreaker._lock",
    ),
    # Attribution poller lock -> leaf instruments: _apply/_audit run
    # under the poller lock and emit flight events + anomaly
    # observations (neither ever calls back into the poller).
    (
        "k8s_device_plugin_tpu/plugin/attribution.py:PodAttributionPoller._lock",
        "k8s_device_plugin_tpu/utils/flight.py:FlightRecorder._lock",
    ),
    (
        "k8s_device_plugin_tpu/plugin/attribution.py:PodAttributionPoller._lock",
        "k8s_device_plugin_tpu/utils/anomaly.py:AnomalyMonitor._lock",
    ),
    # DevicePlugin state condition -> flight ring (ListAndWatch updates
    # are journaled while the state condition is held; the recorder is
    # a leaf).
    (
        "k8s_device_plugin_tpu/plugin/server.py:TpuDevicePlugin._cond",
        "k8s_device_plugin_tpu/utils/flight.py:FlightRecorder._lock",
    ),
}

# ------------------------------------------------- blocking-under-lock
#
# Fully-dotted callables that can block indefinitely.
BLOCKING_DOTTED: set = {
    "time.sleep",
    "urllib.request.urlopen",
    "socket.create_connection",
    "socket.getaddrinfo",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
    "jax.block_until_ready",
}
# Method names that block regardless of receiver (device readback,
# socket/HTTP dials, subprocess drains).
BLOCKING_METHODS: set = {
    "block_until_ready",
    "getresponse",
    "urlopen",
    "communicate",
    "connect",
    "accept",
    "recv",
    "recv_into",
    "sendall",
}
# Methods that are unbounded ONLY without a timeout: Condition/Event
# wait, Queue.get (no-arg form — dict.get always takes a key), join
# (no-arg form — str.join takes an iterable).
BLOCKING_NEED_TIMEOUT: set = {"wait", "wait_for", "get", "join"}

# ------------------------------------------------------- guarded-by
#
# Mutating container/method names: calling one of these on an annotated
# attribute requires the declared lock.  Reads stay unguarded — same
# policy as racecheck.GuardedDeque (lock-free gauge reads are a feature;
# off-lock mutation never is).
MUTATOR_METHODS: set = {
    "append",
    "appendleft",
    "extend",
    "extendleft",
    "insert",
    "remove",
    "pop",
    "popleft",
    "popitem",
    "clear",
    "rotate",
    "add",
    "discard",
    "update",
    "setdefault",
    "sort",
    "put",
}
# Guard markers that delegate to a RUNTIME discipline instead of a
# static with-block: utils/racecheck.py's OwnerGuard single-owner
# contract.  The static pass validates the annotation exists and leaves
# enforcement to the racecheck-enabled suites.
RUNTIME_GUARDS: set = {"owner-thread"}

# ----------------------------------------------------- catalog-drift
#
# Doc files (repo-relative) holding each machine-checked catalog.
EVENT_CATALOG_DOCS = ["docs/operations.md"]
METRIC_CATALOG_DOCS = ["docs/operations.md"]
# Span operation names (utils/spans.py recorders) vs the operations.md
# "Distributed tracing" span-name catalog (header `| Span | Source |`),
# both directions with f-string prefix wildcards — the names the trace
# assembler and operators grep by must stay real.
SPAN_CATALOG_DOCS = ["docs/operations.md"]
FAILPOINT_CATALOG_DOCS = ["docs/chaos.md"]
ENDPOINT_CATALOG_DOCS = ["README.md", "docs/operations.md"]
# Flags: coverage is satisfied by a backticked `--flag` anywhere in the
# operator docs; ghosts are checked against README.md only (the flag
# tables live there), with tools/ CLIs included in the flag universe so
# `tools/chaos_report.py --run` mentions aren't false ghosts.
FLAG_COVERAGE_DOCS = ["README.md", "docs/*.md"]  # globs expanded in the pass
FLAG_GHOST_DOCS = ["README.md"]

# The CLIs whose argparse flags the drift pass checks (repo-relative).
CLI_MODULES = [
    "k8s_device_plugin_tpu/plugin/cli.py",
    "k8s_device_plugin_tpu/models/http_server.py",
    "k8s_device_plugin_tpu/models/benchmark.py",
    "k8s_device_plugin_tpu/router/server.py",
    "k8s_device_plugin_tpu/models/engine.py",
    "k8s_device_plugin_tpu/controller/__main__.py",
    "tools/postmortem.py",
]
# Extra argparse modules whose flags exist but are NOT doc-checked
# (tools/ scripts and the benchmark's harness document themselves in
# their --help); they still widen the ghost-check universe.
FLAG_UNIVERSE_EXTRA_ROOTS = ["tools", "chipbench"]
