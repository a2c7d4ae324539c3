"""Always-on per-step engine profiler: phase breakdown, occupancy, memory.

The step-time histogram (PR 1's ``tpu_engine_step_seconds``) says a step
got slow; it cannot say WHERE — admission scheduling, a long prefill
chunk, the jitted decode dispatch, host-side sample consumption, or a
speculative verify round.  This profiler times those phases on every
step (two ``perf_counter`` reads per phase — cheap enough to never turn
off), tracks batch occupancy and KV-page utilization, and keeps rolling
windows so ``GET /debug/profile`` can answer with p50/p99 per phase over
the recent past.

One construct, three sinks: ``with profiler.phase(name):`` is the ONE
place a phase of the owner loop is opened and closed.  Closing it adds
to the phase's lifetime seconds (``GET /debug/profile``), to its
``tpu_engine_loop_*_seconds_total`` counter on ``/metrics``, and ends a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>``, so in any
capture (``POST /debug/trace``, ``/debug/profile/capture``) the phase
lies on the profiler's own clock beside the device's operations.  With
no capture running an annotation is a flag test.

Phases nest (``prefill.graft`` inside ``prefill``): a child's seconds
also count in every enclosing phase, so a parent's self time is its
seconds minus its children's.  In a capture the annotations are FLAT:
opening a child ends the parent's event and closing it starts a new
one, so at any instant only the innermost open phase has an event and
no event encloses a step.  (A gap labeller that names a device gap
after the host event covering MOST of it would otherwise give every
gap to the enclosing event.)

At the same instants the owner thread's CPU clock (``time.thread_time``)
is read, and its seconds go FLAT to the innermost open phase, as the
annotations do: ``cpu_s`` beside ``total_s`` in ``GET /debug/profile``.
A phase whose CPU seconds fall well short of its wall seconds waits (for
the chip, or for the GIL other threads hold); one that burns its wall
seconds runs its own Python.  One counter takes the CPU of the host's
own work: every step phase but the waits for the chip (``readback``,
``prefill.first_token``) and ``spec_verify``.

Every ``summary_every`` steps a compact aggregate goes into the flight
recorder (utils/flight.py) as an ``engine.step`` event — the black box
carries the performance timeline alongside the lifecycle events — and
each step's wall time feeds the anomaly monitor when one is wired.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

# Host-observable step phases, in execution order.  "schedule" covers
# admission + cancel sweeps, "prefill" the chunked prefill advance and
# graft/activation, "dispatch" the decode enqueue(s) (two per step when
# the overlapped pipeline primes the next step before the readback),
# "readback" the blocking device→host sync of the consumed step (in the
# synchronous loop this includes the device compute — the old "decode"
# phase), "sample" the host-side consumption when nothing is in flight,
# "host_gap" the same consumption when it overlaps the next step's
# device compute (the gap the accelerator used to idle through — a
# well-overlapped engine shows host_gap ≈ the old sample time with
# readback shrunk toward pure transfer), and "spec_verify" the whole
# speculative draft+verify round (which replaces all of the above on
# speculative engines).
PHASES = (
    "schedule", "prefill", "dispatch", "readback", "sample", "host_gap",
    "spec_verify",
)
# The owner loop's wait for work (http_server._loop): with the seven
# step phases it partitions the owner thread's time.
IDLE = "idle"
# Finer phases, each opened inside the body of the one function it
# times.  The prefix names the step phase it usually nests in;
# "finish.clear_slot" nests in whichever phase ends a request (sample or
# host_gap on EOS/max_new, schedule on a cancel, dispatch.frontier on a
# preemption).
SUB_PHASES = (
    "schedule.start_prefill",  # engine_admission._start_prefill
    "prefill.chunk",           # the jit_run dispatch of one prefill chunk
    "prefill.graft",           # engine_paging._graft: prompt K/V -> pages
    "dispatch.frontier",       # engine_paging._ensure_frontier
    "finish.clear_slot",       # engine_paging._clear_slot
    # engine_admission._sample_first_token: waits for the prefill program;
    # in schedule for a handoff admission.
    "prefill.first_token",
    "dispatch.state",          # engine._rebuild_device_state
    "dispatch.enqueue",        # the jit_step / jit_block call itself
    "finish.realign",          # engine._realign_seq_lens
)
ALL_PHASES = PHASES + (IDLE,) + SUB_PHASES
# Phases whose CPU seconds the host-CPU counter leaves out: the two that
# wait for the chip, the speculative round, and the wait for work.
NOT_HOST_CPU = frozenset({"readback", "prefill.first_token", "spec_verify", IDLE})


class _Phase:
    """The reusable context manager ``EngineProfiler.phase`` hands out:
    all per-entry state lives on the profiler's stack."""

    __slots__ = ("_prof", "_name")

    def __init__(self, prof: "EngineProfiler", name: str):
        self._prof, self._name = prof, name

    def __enter__(self) -> None:
        self._prof._open(self._name)

    def __exit__(self, *exc) -> None:
        self._prof._close()


def in_phase(name: str):
    """Run a ServingEngine method inside ``self.profiler.phase(name)`` —
    for a phase that IS one whole function body."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            with self.profiler.phase(name):
                return fn(self, *args, **kwargs)

        return inner

    return wrap


def _percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted window."""
    if not sorted_values:
        return 0.0
    idx = min(int(q * len(sorted_values)), len(sorted_values) - 1)
    return sorted_values[idx]


class EngineProfiler:
    """Rolling-window per-step profile of one ServingEngine.

    ``window`` bounds host memory (one small dict per step).  ``flight``
    receives an ``engine.step`` aggregate every ``summary_every`` steps;
    ``observe_step`` (wired to the anomaly monitor) receives every
    step's wall seconds.  ``seconds`` and ``counts`` map a phase name to
    the ``/metrics`` counter that takes its seconds / its closes
    (engine_types.EngineMetrics.loop_seconds, .loop_counts).
    ``host_cpu`` takes the CPU seconds of the host's own work
    (``NOT_HOST_CPU``); ``thread_clock`` is the owner thread's CPU clock.
    ``snapshot()`` is the JSON body of ``GET /debug/profile``; phases
    are opened and closed on the engine owner thread only, readers run
    on HTTP handler threads — hence the lock.
    """

    def __init__(
        self,
        window: int = 256,
        flight=None,
        summary_every: int = 64,
        observe_step=None,
        seconds=None,
        counts=None,
        host_cpu=None,
        thread_clock=time.thread_time,
    ):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.flight = flight
        self.summary_every = max(int(summary_every), 1)
        self.observe_step = observe_step
        self._seconds = dict(seconds or {})
        self._counts = dict(counts or {})
        self._host_cpu = host_cpu
        self._thread_clock = thread_clock
        self._lock = threading.Lock()
        self._window: deque[dict] = deque(maxlen=window)
        self.steps = 0
        self.tokens = 0
        self._phase_totals = {p: 0.0 for p in ALL_PHASES}
        self._cpu_totals = {p: 0.0 for p in ALL_PHASES}
        self._phase_cms = {p: _Phase(self, p) for p in ALL_PHASES}
        # Owner-thread-only: the open phases, innermost last, as
        # (name, opened_at); the innermost one's live annotation; and the
        # per-phase seconds of the step in flight (None outside step()).
        self._stack: list[tuple[str, float]] = []
        self._annotation = None
        self._step_phases: Optional[dict[str, float]] = None
        self._step_t0 = 0.0
        # Where the step's last top-level phase closed, and its name.
        self._step_mark = 0.0
        self._step_last: Optional[str] = None
        # The thread clock at the last instant its seconds were given out.
        self._cpu_mark = 0.0
        self._mem_fn = "unprobed"  # "unprobed" -> callable | None

    # --------------------------------------------------------------- phases

    def phase(self, name: str) -> _Phase:
        """``with profiler.phase("readback"): ...`` — owner thread only."""
        return self._phase_cms[name]

    def _open(self, name: str) -> None:
        if self._annotation is not None:
            # Flat in the capture: the parent's event ends here and a new
            # one starts when this child closes.
            self._annotation.__exit__(None, None, None)
        now = time.perf_counter()
        cpu = self._thread_clock()
        if self._stack:
            self._add_cpu(self._stack[-1][0], cpu)
        elif self._step_phases is not None:
            # Inside a step the top-level phases are contiguous: each
            # runs from where the previous one closed (the first from the
            # step's start), so they sum to the step's wall time and the
            # few microseconds of step() between them are not lost.
            # The CPU of those microseconds goes the same way.
            now = self._step_mark
            self._add_cpu(name, cpu)
        self._cpu_mark = cpu
        self._stack.append((name, now))
        # The event starts at construction (jaxlib TraceMe), not __enter__.
        self._annotation = TraceAnnotation("engine." + name)

    def _close(self) -> None:
        self._annotation.__exit__(None, None, None)
        cpu = self._thread_clock()
        name, t0 = self._stack[-1]
        self._add_cpu(name, cpu)
        self._stack.pop()
        now = time.perf_counter()
        if self._stack:
            self._annotation = TraceAnnotation("engine." + self._stack[-1][0])
        else:
            self._annotation = None
            self._step_mark, self._step_last = now, name
        self._add(name, now - t0)
        sink = self._counts.get(name)
        if sink is not None:
            sink.inc()

    def _add_cpu(self, name: str, cpu: float) -> None:
        """The owner thread's CPU since the last mark, to ``name``: the
        innermost open phase (or, at a step's edges, the top-level phase
        the wall time goes to)."""
        dt = cpu - self._cpu_mark
        self._cpu_mark = cpu
        with self._lock:
            self._cpu_totals[name] += dt
        # The top-level phase ``name`` runs under counts too: a finish
        # inside a speculative round is that round's.
        root = self._stack[0][0] if self._stack else name
        if (
            self._host_cpu is not None
            and name not in NOT_HOST_CPU
            and root not in NOT_HOST_CPU
        ):
            self._host_cpu.inc(dt)

    def _add(self, name: str, dt: float) -> None:
        """``dt`` more seconds of ``name`` into all three records."""
        if self._step_phases is not None:
            self._step_phases[name] = self._step_phases.get(name, 0.0) + dt
        with self._lock:
            self._phase_totals[name] += dt
        sink = self._seconds.get(name)
        if sink is not None:
            sink.inc(dt)

    # -------------------------------------------------------------- memory

    def _memory_bytes(self) -> Optional[int]:
        """Device bytes-in-use via PJRT memory_stats, when the backend
        exposes it (TPU does; CPU returns None) — probed once.  Read by
        ``snapshot()`` and the periodic flight summary, never per step:
        the loop this profiler times is the replica's bottleneck."""
        if self._mem_fn == "unprobed":
            self._mem_fn = None
            try:
                import jax

                dev = jax.local_devices()[0]
                stats = dev.memory_stats()
                if stats and "bytes_in_use" in stats:
                    self._mem_fn = lambda d=dev: d.memory_stats()["bytes_in_use"]
            except Exception:
                self._mem_fn = None
        if self._mem_fn is None:
            return None
        try:
            return int(self._mem_fn())
        except Exception:
            self._mem_fn = None
            return None

    # --------------------------------------------------------------- record

    def begin_step(self) -> None:
        """Open one step's record; phases closed until ``finish_step``
        also land in its per-step breakdown."""
        self._step_phases = {}
        self._step_t0 = self._step_mark = time.perf_counter()
        self._cpu_mark = self._thread_clock()
        self._step_last = None

    def finish_step(
        self,
        *,
        active_slots: int,
        max_slots: int,
        queued: int,
        kv_page_utilization: float,
        tokens: int,
        overlap_hits: int = 0,
        overlap_discards: int = 0,
        kvcache_hits: int = 0,
        kvcache_restores: int = 0,
    ) -> float:
        """Close out the step ``begin_step`` opened: fold its phases into
        the window, emit the periodic flight summary, feed the anomaly
        hook.  ``overlap_hits``/``overlap_discards`` are THIS step's
        deltas from the engine's overlapped-pipeline counters (a hit =
        the step was consumed from an in-flight dispatch; a discard = a
        wasted lane); ``kvcache_hits``/``kvcache_restores`` likewise from
        the KV tiering counters (pages served from a tier / restored
        host->device this step).  Returns the step's wall seconds."""
        now = time.perf_counter()
        if self._step_last is not None:
            # step()'s own wrap-up counts in the phase that ran last.
            self._add_cpu(self._step_last, self._thread_clock())
            self._add(self._step_last, now - self._step_mark)
        wall = now - self._step_t0
        record = {
            "wall_s": wall,
            "phases": self._step_phases or {},
            "active_slots": active_slots,
            "queued": queued,
            "kv_page_utilization": kv_page_utilization,
            "tokens": tokens,
            "overlap_hits": overlap_hits,
            "overlap_discards": overlap_discards,
            "kvcache_hits": kvcache_hits,
            "kvcache_restores": kvcache_restores,
        }
        self._step_phases = None
        with self._lock:
            self._window.append(record)
            self.steps += 1
            self.tokens += tokens
            emit_summary = (
                self.flight is not None and self.steps % self.summary_every == 0
            )
            if emit_summary:
                window = list(self._window)
        if emit_summary:
            walls = sorted(r["wall_s"] for r in window)
            mem = self._memory_bytes()
            self.flight.record(
                "engine.step",
                steps=self.steps,
                window=len(window),
                step_ms_p50=round(_percentile(walls, 0.5) * 1e3, 3),
                step_ms_p99=round(_percentile(walls, 0.99) * 1e3, 3),
                active_slots=active_slots,
                queued=queued,
                kv_page_utilization=round(kv_page_utilization, 4),
                tokens_per_step=round(
                    sum(r["tokens"] for r in window) / len(window), 2
                ),
                occupancy=round(
                    sum(r["active_slots"] for r in window)
                    / (len(window) * max(max_slots, 1)),
                    4,
                ),
                # Overlap health over the window: hit ratio near 1.0
                # means steady decode consumed almost every step from an
                # in-flight dispatch; a discard-heavy ratio says traffic
                # churns faster than the pipeline can stay primed.
                overlap_hit_ratio=round(
                    sum(r.get("overlap_hits", 0) for r in window)
                    / len(window),
                    4,
                ),
                overlap_discards=sum(
                    r.get("overlap_discards", 0) for r in window
                ),
                kvcache_hits=sum(r.get("kvcache_hits", 0) for r in window),
                kvcache_restores=sum(
                    r.get("kvcache_restores", 0) for r in window
                ),
                **({"mem_bytes": mem} if mem is not None else {}),
            )
        if self.observe_step is not None:
            self.observe_step(wall)
        return wall

    # ------------------------------------------------------------- snapshot

    def snapshot(self) -> dict:
        """JSON body for ``GET /debug/profile``: per-phase breakdown
        (mean/p50/p99 over the rolling window, lifetime totals), batch
        occupancy, KV-page utilization, and device memory in use now."""
        with self._lock:
            window = list(self._window)
            steps = self.steps
            tokens = self.tokens
            totals = dict(self._phase_totals)
            cpu = dict(self._cpu_totals)
        n = len(window)
        phases = {}
        for phase in ALL_PHASES:
            samples = sorted(r["phases"].get(phase, 0.0) for r in window)
            in_window = [r for r in window if phase in r["phases"]]
            phases[phase] = {
                "total_s": round(totals[phase], 6),
                # Flat: the owner thread's CPU while this phase was the
                # innermost open one (its children's is theirs).
                "cpu_s": round(cpu[phase], 6),
                "window_mean_ms": round(
                    (sum(samples) / n * 1e3) if n else 0.0, 4
                ),
                "window_p50_ms": round(_percentile(samples, 0.5) * 1e3, 4),
                "window_p99_ms": round(_percentile(samples, 0.99) * 1e3, 4),
                "window_steps": len(in_window),
            }
        walls = sorted(r["wall_s"] for r in window)
        mem = self._memory_bytes()
        return {
            "steps": steps,
            "tokens": tokens,
            "window": n,
            "step_ms": {
                "mean": round((sum(walls) / n * 1e3) if n else 0.0, 4),
                "p50": round(_percentile(walls, 0.5) * 1e3, 4),
                "p99": round(_percentile(walls, 0.99) * 1e3, 4),
            },
            "phases": phases,
            "occupancy": {
                "mean_active_slots": round(
                    sum(r["active_slots"] for r in window) / n, 3
                )
                if n
                else 0.0,
                "mean_queued": round(sum(r["queued"] for r in window) / n, 3)
                if n
                else 0.0,
                "mean_kv_page_utilization": round(
                    sum(r["kv_page_utilization"] for r in window) / n, 4
                )
                if n
                else 0.0,
            },
            "tokens_per_step_mean": round(
                sum(r["tokens"] for r in window) / n, 3
            )
            if n
            else 0.0,
            "overlap": {
                "window_hits": sum(
                    r.get("overlap_hits", 0) for r in window
                ),
                "window_discards": sum(
                    r.get("overlap_discards", 0) for r in window
                ),
                "hit_ratio": round(
                    sum(r.get("overlap_hits", 0) for r in window) / n, 4
                )
                if n
                else 0.0,
            },
            "kvcache": {
                "window_hits": sum(r.get("kvcache_hits", 0) for r in window),
                "window_restores": sum(
                    r.get("kvcache_restores", 0) for r in window
                ),
            },
            "device_memory": {"bytes_in_use": mem} if mem is not None else None,
        }
