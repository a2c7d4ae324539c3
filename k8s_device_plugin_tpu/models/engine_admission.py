"""Serving-engine admission policy: validation, queueing, prefill, finish.

Split out of engine.py (round 4): the request lifecycle from submit()
through batched prefill to slot activation and the finish conditions,
mixed into ServingEngine (which owns the queue, slots, and cache).  Page
accounting it triggers lives in engine_paging.py; the jitted decode steps
in engine_sampling.py.
"""

from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import failpoints
from ..utils.spans import new_trace_id
from .engine_overload import (
    PRIORITY_NAMES,
    SHED_EXPIRED,
    SHED_INFEASIBLE,
    ShedError,
    parse_priority,
)
from .engine_profiler import in_phase
from .engine_sampling import _token_logprob, filter_top_k_top_p, moe_stats
from .engine_types import Request
from .transformer import decode_cache_spec


class AdmissionMixin:
    """submit/cancel, the batched chunked prefill pipeline, admission into
    slots, and the per-request finish conditions."""

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        adapter: Optional[int] = None,
        logprobs: bool = False,
        stop: Optional[list] = None,
        logit_bias: Optional[dict] = None,
        trace_id: Optional[str] = None,
        trace_parent: str = "",
        trace_hop: int = 0,
        trace_attempt: int = 0,
        priority: int = 1,
        tenant: str = "",
        deadline_s: Optional[float] = None,
    ) -> Request:
        try:
            prompt, stop, logit_bias, priority, tenant, deadline_s = (
                self._validate_submit(
                    prompt, max_new_tokens, temperature, top_k, top_p,
                    adapter, logprobs, stop, logit_bias,
                    priority, tenant, deadline_s,
                )
            )
        except (TypeError, ValueError) as e:
            # Admission rejects are flight-recorder events: a burst of
            # them right before an incident is exactly the kind of
            # lead-up the black box exists to preserve (and rejects
            # never reach the metrics/span paths — the request dies
            # before it has a lifecycle).
            if self.flight is not None:
                try:
                    ptoks = len(prompt)  # may be unsized/hostile input
                except TypeError:
                    ptoks = None
                self.flight.record(
                    "admission.reject",
                    reason=str(e),
                    prompt_tokens=ptoks,
                    max_new_tokens=max_new_tokens,
                )
            raise
        try:
            # Chaos seam (docs/chaos.md): error rejects an otherwise-
            # valid request at the admission door (surfacing as a 422 on
            # the HTTP path, like any rejection); delay stalls admission
            # without touching the compiled path.
            failpoints.fire("engine.submit", prompt_tokens=len(prompt))
        except failpoints.FailpointError as e:
            if self.flight is not None:
                self.flight.record(
                    "admission.reject",
                    reason=str(e),
                    prompt_tokens=len(prompt),
                    max_new_tokens=max_new_tokens,
                )
            raise ValueError(str(e)) from None
        with self._lock:
            now = time.monotonic()
            deadline = None if deadline_s is None else now + deadline_s
            if self.overload is not None:
                # Submit-side overload gate: an already-expired deadline
                # fails fast (504 on the HTTP path — never enqueued,
                # never holds pages), and the adaptive shedder rejects
                # lowest-priority first when the projected queue wait
                # runs past the class headroom (503 + honest
                # Retry-After from the measured drain rate).
                try:
                    if deadline is not None and deadline <= now:
                        raise ShedError(
                            "deadline expired before admission",
                            SHED_EXPIRED,
                            0.0,
                        )
                    # Only what the free slots cannot take at the next
                    # step waits at all: a burst of eight into eight idle
                    # slots must not shed because the drain-rate estimate
                    # was learned from serial traffic (chip_smoke.py's
                    # warm-ups, then its concurrent burst — PR 21).
                    active = sum(1 for r in self.slots if r is not None)
                    room = max(
                        0,
                        min(self.max_slots, self.overload.concurrency_limit())
                        - active,
                    )
                    self.overload.check_admission(
                        priority, max(0, len(self.queue) - room)
                    )
                except ShedError as e:
                    self.overload.record_shed(
                        None,
                        e.kind,
                        priority=priority,
                        tenant=tenant,
                        prompt_tokens=len(prompt),
                        at="submit",
                    )
                    self._slo_observe_submit_shed(tenant)
                    raise
            req = Request(
                prompt, max_new_tokens, temperature, top_k, top_p,
                adapter=adapter, logprobs=logprobs, stop=stop,
                logit_bias=logit_bias,
                priority=priority, tenant=tenant, deadline=deadline,
                # Every request is traceable even when the caller didn't
                # send an id — generated ids tie SSE events, spans, and
                # log lines of one request together.
                trace_id=trace_id or new_trace_id(),
                # Cross-process parent (router attempt span) from the
                # X-Trace-Context hop header, when one arrived.
                trace_parent=str(trace_parent or ""),
                trace_hop=int(trace_hop), trace_attempt=int(trace_attempt),
                rid=self._next_rid, submitted_at=now,
            )
            if self.spans:
                # Root span id reserved NOW so the queue/prefill/decode
                # children (recorded from the owner thread) can parent on
                # it before the root itself is recorded at finish.
                req.root_span = self.spans.reserve_id()
            self._next_rid += 1
            self.queue.append(req)
            # Scrapes happen on the MetricsServer thread: reflect queue
            # pressure immediately, not at the owner's next step().
            self._update_gauges()
        return req

    MAX_TENANT_LEN = 64

    def _validate_submit(
        self, prompt, max_new_tokens, temperature, top_k, top_p,
        adapter, logprobs, stop, logit_bias,
        priority=1, tenant="", deadline_s=None,
    ) -> tuple:
        """Normalize and validate one submit()'s arguments; raises
        ValueError/TypeError on anything inadmissible (the one seam
        submit() wraps to meter rejects).  Returns the normalized
        (prompt, stop, logit_bias, priority, tenant, deadline_s)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        priority = parse_priority(priority)
        tenant = str(tenant or "")
        if len(tenant) > self.MAX_TENANT_LEN:
            raise ValueError(
                f"tenant is capped at {self.MAX_TENANT_LEN} chars, "
                f"got {len(tenant)}"
            )
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if not math.isfinite(deadline_s):
                raise ValueError(f"deadline_s must be finite, got {deadline_s}")
        if stop is not None:
            stop = [[int(t) for t in seq] for seq in stop]
            if not stop or any(not seq for seq in stop):
                raise ValueError(
                    "stop must be a non-empty list of non-empty "
                    "token-id sequences"
                )
            # _hit_stop is O(num_stops x stop_len) Python compares on the
            # owner thread per emitted token; an uncapped list from the
            # unauthenticated HTTP endpoint could stall the serving loop
            # for every tenant, so cap like logit_bias caps MAX_BIAS.
            if len(stop) > self.MAX_STOPS:
                raise ValueError(
                    f"at most {self.MAX_STOPS} stop sequences, got {len(stop)}"
                )
            too_long = [seq for seq in stop if len(seq) > self.MAX_STOP_LEN]
            if too_long:
                raise ValueError(
                    f"stop sequences are capped at {self.MAX_STOP_LEN} "
                    f"tokens, got one of length {max(len(s) for s in too_long)}"
                )
        if logit_bias is not None:
            logit_bias = {int(t): float(v) for t, v in logit_bias.items()}
            if not logit_bias or len(logit_bias) > self.MAX_BIAS:
                raise ValueError(
                    f"logit_bias must have 1..{self.MAX_BIAS} entries, "
                    f"got {len(logit_bias)}"
                )
            bad = [t for t in logit_bias if not 0 <= t < self.cfg.vocab_size]
            if bad:
                raise ValueError(f"logit_bias ids out of vocab range: {bad}")
            if self._spec_gamma:
                # The round's draft/verify acceptance math scores the
                # UNBIASED distributions; biasing only the emitted pick
                # would break the exactness guarantee.
                raise ValueError(
                    "logit_bias is not supported on a speculative engine"
                )
        if logprobs and self._spec_gamma:
            # The speculative round emits accepted draft tokens without
            # materializing their target log-softmax; scoring them would
            # need an extra pass per round.  Pick one per engine.
            raise ValueError(
                "logprobs is not supported on a speculative engine "
                "(spec_gamma > 0)"
            )
        if adapter is not None:
            if not self.cfg.lora_serve:
                raise ValueError(
                    "adapter requires an engine built with cfg.lora_serve"
                )
            if not 0 <= adapter < self.cfg.lora_serve:
                raise ValueError(
                    f"adapter must be in [0, {self.cfg.lora_serve}), "
                    f"got {adapter}"
                )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and not 1 <= top_k <= self.cfg.vocab_size:
            raise ValueError(
                f"top_k must be in [1, vocab_size={self.cfg.vocab_size}], "
                f"got {top_k}"
            )
        if top_p is not None and not 0 < top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # Speculative rounds write up to gamma positions past the accepted
        # point before the host rewinds, so every capacity bound carries
        # that headroom (= models/speculative.py's max_seq check).
        need = len(prompt) + max_new_tokens + self._spec_gamma
        if need > self.paged.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens}"
                + (
                    f" + spec headroom {self._spec_gamma}"
                    if self._spec_gamma
                    else ""
                )
                + f" exceeds paged max_len {self.paged.max_len}"
            )
        # Admissibility, not just addressability: the request must fit the
        # ALLOCATABLE pool (page 0 is reserved), else it would block the
        # FIFO head forever.
        allocatable = (self.paged.num_pages - 1) * self.paged.page_size
        if need > allocatable:
            raise ValueError(
                f"request needs {need} cache slots but the pool only ever "
                f"has {allocatable} ({self.paged.num_pages - 1} allocatable "
                f"pages x {self.paged.page_size})"
            )
        return prompt, stop, logit_bias, priority, tenant, deadline_s

    def cancel(self, req: Request) -> bool:
        """Stop generating for ``req`` (the client went away — the HTTP
        front-end calls this on disconnect/timeout so an abandoned
        request stops burning chip time).  Thread-safe like submit().

        A still-queued request finishes right here (it holds no pages);
        an in-flight one is marked and the owner thread tears it down at
        its next step boundary — slot, pages, and prefix refcounts all
        return through the ordinary _clear_slot path, so the pool stays
        exact.  Returns False if the request had already finished."""
        with self._lock:
            if req.done:
                return False
            req.cancelled = True
            try:
                self.queue.remove(req)
            except ValueError:
                pass  # admitted (slot or mid-prefill): next step cleans up
            else:
                req.done = True
                # A preempted request dying in the queue will never
                # resume: release its host-arena snapshot bytes now.
                self._kv_drop_snapshot(req.rid)
                if self.overload is not None:
                    self.overload.on_finish(req)
                # Excluded from SLI verdicts (the client left, the
                # service didn't fail) but still metered.
                self._slo_observe_finish(req, time.monotonic())
            self._update_gauges()
            return True

    def _overload_sweep(self) -> list["Request"]:
        """Overload-control step work (step() calls this before
        admission, only when a controller is installed): shed queued
        requests whose deadline passed, preempt in-slot requests that
        can no longer finish in time, and tick the AIMD limiter.
        Returns the queued requests shed here (already done) so step()
        reports them like any other finish."""
        ctl = self.overload
        now = time.monotonic()
        finished: list[Request] = []
        with self._lock:
            expired = [
                r for r in self.queue if not r.cancelled and ctl.expired(r, now)
            ]
            for req in expired:
                # Shed from the queue: the request never held a slot or
                # a page — it simply stops existing, and its waiter is
                # answered (504) instead of burning capacity on a
                # response nobody can use anymore.
                self.queue.remove(req)
                req.shed = SHED_EXPIRED
                req.done = True
                req.finished_at = now
                self._kv_drop_snapshot(req.rid)
                ctl.record_shed(
                    req, SHED_EXPIRED,
                    waited_s=round(now - req.submitted_at, 3),
                )
                ctl.on_finish(req)
                # Queue sheds never reach _maybe_finish: emit their
                # availability verdict + usage row here.
                self._slo_observe_finish(req, now)
                finished.append(req)
            if expired:
                self._update_gauges()
        # In-slot preemption: a ready slot whose deadline passed — or
        # whose remaining token budget cannot fit the remaining time at
        # the measured per-token latency — sheds NOW instead of decoding
        # a tail the client will never accept.  Marking cancelled reuses
        # the ordinary teardown (step()'s cancel sweep → _maybe_finish →
        # _clear_slot), so the slot and its pages return through the
        # exact path every other teardown uses.
        for s in range(self.max_slots):
            req = self.slots[s]
            if (
                req is None
                or req.done
                or req.cancelled
                or req.shed is not None
                or not self._slot_ready[s]
            ):
                continue
            if ctl.infeasible(req, now):
                req.shed = SHED_INFEASIBLE
                req.cancelled = True
                ctl.record_shed(
                    req, SHED_INFEASIBLE,
                    slot=s,
                    remaining_tokens=req.max_new_tokens - len(req.tokens),
                    remaining_s=round((req.deadline or now) - now, 3),
                )
        ctl.maybe_adjust()
        return finished

    def _prefill_chunk_fn(self, chunk: int, batch: int, bucket: int):
        """Jitted CHUNK prefill: one multi-token cached append of ``chunk``
        tokens at traced offset pos0 into a carried dense cache.  One
        compiled program per (chunk, batch, bucket) triple serves every
        chunk index of its bucket (the unchunked path is simply
        chunk == bucket; the bucket keys the cache SIZE the chunk scores
        against — see ServingEngine._dense_chunk_model).  Cached on THIS
        instance (a process-global lru_cache would pin the engine —
        params tree and page pools included — beyond its lifetime).  The
        carried cache is donated: the host rebinds job["cache"] from the
        output, so without donation every chunk would copy the whole
        [batch, bucket] dense cache."""
        key = (chunk, batch, bucket)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        # First (chunk, batch, bucket) shape: the dispatch below compiles.
        self._wd_grace(f"compile:prefill_{chunk}x{batch}x{bucket}")
        model = self._dense_chunk_model(bucket)

        moe = model.config.moe is not None

        def run(params, cache, tokens, pos0, last_idx, aids, *real_rows):
            pos = jnp.broadcast_to(
                pos0 + jnp.arange(chunk)[None, :], (batch, chunk)
            )
            # Each row's true-last-position logits, valid only when
            # last_idx falls inside this chunk (the host keeps the row
            # from the covering chunk).  A function, called where each
            # path needs it: a model that keeps all logits traces the
            # operations in the order, and so to the program, it had.
            def last_row():
                return jnp.clip(last_idx - pos0, 0, chunk - 1)

            # last_positions: a mixer's recurrent state must not see the
            # bucket's padding (models/ssm.py); attention ignores it.
            # logits_at: a config that keeps one logit a prompt sends
            # only the selected position through the head.
            keep_one = model.config.logits_to_keep == 1
            more = {}
            if moe:
                # A model with expert layers (models/moe.py) is told its
                # real tokens: a prompt's positions, in the job's real
                # rows (``real_rows``: the batch is padded to a power of
                # two).  The rest routes nowhere and counts nothing.
                more["token_mask"] = (pos <= last_idx[:, None]) & (
                    jnp.arange(batch)[:, None] < real_rows[0]
                )
            logits, mut = model.apply(
                {"params": params, "cache": cache}, tokens, pos,
                adapter_ids=aids,
                last_positions=last_idx,
                logits_at=last_row() if keep_one else None,
                mutable=["cache", "moe_stats"] if moe else ["cache"],
                **more,
            )
            # The chunk's routing counts leave as a third output, read
            # when the job's logits are (_activate): no sync of its own.
            stats = (moe_stats(mut),) if moe else ()
            if keep_one:
                return (logits[:, 0], mut["cache"], *stats)
            sel = last_row()
            return (logits[jnp.arange(batch), sel], mut["cache"], *stats)

        fn = jax.jit(run, donate_argnums=(1,))
        self._prefill_cache[key] = fn
        return fn

    def _zero_prefill_cache(self, bucket: int, batch: int):
        """The zero dense cache a prefill job starts from: ONE dispatch
        of one compiled program of NO operands per (bucket, batch),
        whatever the number of leaves (K, V and an index a layer, a
        mixer's state and convolution tail beside them) — an eager
        ``jnp.zeros`` a leaf costs the owner loop a dispatch each, the
        dominant per-admission host cost.  The spec is an abstract
        trace of the whole model (~100ms of host work) and depends only
        on (bucket, batch): it is traced once, when the maker is built,
        and the maker is cached on THIS instance like
        _prefill_chunk_fn.  Every leaf comes back as its own buffer,
        uncommitted on the default device, like the chunk program's
        own output: that program donates the whole tree and is
        compiled once a key."""
        key = (bucket, batch)
        make = self._prefill_cache_makers.get(key)
        if make is None:
            self._wd_grace(f"compile:prefill_cache_{batch}x{bucket}")
            spec = decode_cache_spec(self._dense_chunk_model(bucket), batch)

            def zero_cache():
                return jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), spec
                )

            make = self._prefill_cache_makers[key] = jax.jit(zero_cache)
        self.prefill_cache_dispatches += 1
        if self.metrics:
            self.metrics.prefill_cache_dispatches.inc()
        return make()

    def prefill_cache_state(self) -> dict:
        """The ``prefill_cache`` block of ``GET /debug/profile``: the
        admission groups started (tpu_engine_prefill_jobs_total), the
        dispatches of the zero-cache makers
        (tpu_engine_prefill_cache_dispatches_total; over the jobs it
        reads 1.0) and the compiled makers held, counted from the jit
        caches: one per (bucket, batch) met, so a maker that recompiled
        per prompt length would show."""
        makers = list(self._prefill_cache_makers.values())
        return {
            "jobs": self.prefill_jobs,
            "dispatches": self.prefill_cache_dispatches,
            "programs": sum(fn._cache_size() for fn in makers),
        }

    @in_phase("schedule.start_prefill")
    def _start_prefill(self, items: list[tuple[int, "Request", list[int], int]]):
        """Create one prefill JOB for a same-length-bucket admission group.

        Length padding is sound for two reasons, one per kind of cached
        unit.  Attention is causal — positions >= plen cannot influence
        logits[plen-1] — and _graft copies only rows [:plen] into pages,
        so the padded tail's garbage K/V never leaves the throwaway
        dense cache.  A recurrence is causal too, but its STATE is what
        the graft carries over, and a pad token run through it would
        stay there: the chunk program hands each row's ``last_idx`` to
        the model as ``last_positions``, and the mixer (models/ssm.py)
        freezes the row's state past it (dt = 0: decay 1, input 0) and
        keeps the convolution's tail at the last REAL inputs, which may
        lie in an earlier chunk (tests/test_engine_state.py serves every
        prompt length of one bucket against the plain reference).  A
        resumed request (prompt + tokens) rebuilds its state by the same
        path.  The batch dim is padded to a
        power of two (repeating the first prompt; its extra rows are
        discarded), so an admission burst of N prompts costs ONE dispatch
        per chunk instead of N serial prefills, and the number of
        compiled prefill programs stays O(log max_len * log max_slots).

        Without ``prefill_chunk`` the job is a single full-bucket chunk
        and completes on its first advance (same step() call it was
        admitted in); with chunking, step() advances ONE chunk per call,
        so active slots stall at most one chunk's compute per step while
        a long prompt streams in.

        Decode-role engines (models/engine_handoff.py) additionally SKIP
        the leading chunks every item's shared/restored pages already
        cover: the job's dense cache is SEEDED from those device pages
        (their rows are exactly the bytes a same-bucket recompute would
        write — the content-addressed guarantee the KV tiers already
        rely on) and ``pos`` starts at the first uncovered chunk, so a
        handed-off long prompt costs one tail chunk instead of the whole
        prompt's compute.  The chunk containing each prompt's LAST
        position always runs (the admission token samples from its
        logits).  Unified engines never skip — the historical prefill
        schedule is untouched.
        """
        # Effective prompts: resumed (preempted) requests re-prefill
        # their original prompt PLUS what they had already generated.
        prompts = [it[1].prompt + it[1].tokens for it in items]
        longest = max(len(p) for p in prompts)
        bucket = min(1 << (longest - 1).bit_length(), self.paged.max_len)
        chunk = min(self._prefill_chunk or bucket, bucket)
        n = len(prompts)
        batch = 1 << (n - 1).bit_length()
        rows = [p + [0] * (bucket - len(p)) for p in prompts]
        rows += [rows[0]] * (batch - n)
        last_idx = [len(p) - 1 for p in prompts] + [0] * (batch - n)
        aids = [
            it[1].adapter if it[1].adapter is not None else -1 for it in items
        ]
        aids += [aids[0]] * (batch - n)  # pad rows are discarded anyway
        self.prefill_jobs += 1
        if self.metrics:
            self.metrics.prefill_jobs.inc()
        cache = self._zero_prefill_cache(bucket, batch)
        ps = self.paged.page_size
        skip = 0
        if self._handoff_skip_covered:
            # Chunk-aligned token count covered for EVERY item, capped
            # below every item's last position so the logits-bearing
            # chunk always computes.
            skip = min(
                min(it[3] * ps for it in items),
                min(len(p) for p in prompts) - 1,
            )
            skip -= skip % chunk
        if skip > 0:
            # Seed the covered positions from the items' device pages
            # (restored/shared rows are already on device by admission).
            # One eager slice-set per pool per layer per item; compiles
            # per (batch, bucket, skip) shape like the restore scatter.
            self._wd_grace("handoff_seed")
            for row_idx, it in enumerate(items):
                pages = jnp.asarray(
                    it[2][: -(-skip // ps)], jnp.int32
                )
                for name in self._layer_names:
                    att = self.cache[name]["attn"]
                    src = cache[name]["attn"]
                    new_src = dict(src)
                    for pool in self._kv_pool_names(att):
                        rows_dev = att[pool][pages]
                        rows_dev = rows_dev.reshape(
                            rows_dev.shape[0] * ps, *rows_dev.shape[2:]
                        )[:skip]
                        dense = "cached_" + pool[len("pool_"):]
                        new_src[dense] = (
                            src[dense].at[row_idx, :skip].set(rows_dev)
                        )
                    # The cached append writes K/V at cache_index (one
                    # scalar per layer, shared across the batch): start
                    # it at the first UNCOMPUTED position or the first
                    # computed chunk would clobber the seeded rows.
                    new_src["cache_index"] = jnp.asarray(skip, jnp.int32)
                    cache[name]["attn"] = new_src
            self.handoff_skipped_tokens += skip * len(items)
        self._pending.append(
            {
                "items": items,
                "bucket": bucket,
                "chunk": chunk,
                "batch": batch,
                "rows": jnp.asarray(rows, jnp.int32),
                "last_idx_host": last_idx,
                "last_idx": jnp.asarray(last_idx, jnp.int32),
                "aids": jnp.asarray(aids, jnp.int32),
                "cache": cache,
                "pos": skip,
                "logits": [None] * n,
                # Expert layers only: the real rows of the padded batch,
                # an operand of the chunk program, and the chunks' routing
                # counts, still on the device.
                "real_rows": [jnp.asarray(n, jnp.int32)] if self._moe_shape else [],
                "moe_stats": [],
            }
        )

    def _advance_prefill(self, job: dict) -> bool:
        """Run ONE chunk of a pending prefill job; True when complete."""
        # Prefill work legitimately dwarfs the decode baseline (and may
        # hit a fresh XLA shape): grace the hung-step deadline.
        self._wd_grace("prefill")
        chunk, pos = job["chunk"], job["pos"]
        with self.profiler.phase("prefill.chunk"):
            fn = self._prefill_chunk_fn(chunk, job["batch"], job["bucket"])
            tokens = jax.lax.slice_in_dim(
                job["rows"], pos, pos + chunk, axis=1
            )
            logits_rows, job["cache"], *stats = fn(
                self.params,
                job["cache"],
                tokens,
                jnp.asarray(pos, jnp.int32),
                job["last_idx"],
                job["aids"],
                *job["real_rows"],
            )
            job["moe_stats"] += stats
        for i in range(len(job["items"])):
            if pos <= job["last_idx_host"][i] < pos + chunk:
                job["logits"][i] = logits_rows[i]
        job["pos"] = pos + chunk
        # Chunks past every row's LAST position compute nothing a graft
        # or logit read ever consumes (positions >= plen are masked
        # padding): stop at the chunk containing the deepest last_idx
        # instead of running to the bucket — a prompt just past a
        # power-of-two boundary no longer pays the bucket's full tail.
        if job["pos"] > max(job["last_idx_host"]):
            job["pos"] = job["bucket"]
        if self._handoff_taps:
            # Prefill→decode handoff (engine_handoff.py): stream every
            # newly covered full page to its tapped /v1/prefill handler
            # the moment this chunk's K/V exist — transfer overlaps the
            # remaining prefill compute.  One dict check when no probe
            # is tapped.
            self._handoff_feed(job)
        return job["pos"] >= job["bucket"]

    def _admit(self) -> list[Request]:
        """Admit queued requests into free slots; returns any that finished
        at admission already (EOS or max_new_tokens == 1 on the prefill
        token) so step() can report them.

        Two phases so an admission BURST costs one prefill dispatch per
        length bucket, not one per request (serial per-request prefill was
        the churn-throughput hole): phase 1 assigns
        slots/pages/trie links for everything that fits, phase 2 batches
        the dense prefills by length bucket and grafts each row.
        """
        admitted: list[tuple[int, Request, list[int], int]] = []
        burst_pages: dict[int, int] = {}  # page -> length bucket, this burst
        # Whether this pass left the FIFO head stuck on a page shortage:
        # the decode-block gate reads it — with the head page-blocked,
        # nothing can admit until something frees, so fine-grained
        # stepping buys no admission latency (engine.py _step_inner).
        was_page_blocked = self._admit_page_blocked
        self._admit_page_blocked = False
        for slot in range(self.max_slots):
            # Queue peek/pop under the lock (submit() appends from other
            # threads); everything after the pop touches owner-only state.
            with self._lock:
                # A cancel() racing an eviction can leave a cancelled
                # request at the queue head (see _evict_slot); finish it
                # here instead of prefetching for a dead client.
                while self.queue and self.queue[0].cancelled:
                    dead = self.queue.popleft()
                    dead.done = True
                    self._kv_drop_snapshot(dead.rid)
                    # Cancels are excluded from SLI verdicts but still
                    # metered (the tenant consumed queue time).
                    self._slo_observe_finish(dead, time.monotonic())
                if self.slots[slot] is not None or not self.queue:
                    continue
                if self.overload is not None:
                    # AIMD admitted-concurrency cap: slots beyond the
                    # limit stay idle while the limiter says queue wait
                    # is past target — admitting into them would add
                    # wait for everything already queued.
                    if (
                        sum(1 for r in self.slots if r is not None)
                        >= self.overload.concurrency_limit()
                    ):
                        break
                    # Policy-ordered head: move the selected request
                    # (best priority class, fairest tenant by token-cost
                    # debt, earliest deadline, then arrival) to the
                    # front.  Everything downstream — the restore-resume
                    # fast path and the page-blocked head semantics
                    # included — keeps operating on queue[0], so the
                    # mechanics stay identical to the FIFO engine.
                    idx = self.overload.select_index(self.queue)
                    if idx:
                        chosen = self.queue[idx]
                        del self.queue[idx]
                        self.queue.appendleft(chosen)
                req = self.queue[0]
                # Preempted request back at the head: rebuild its slot
                # from the kv-cache tiers and skip prefill entirely when
                # coverage is complete (engine_kvcache.py); short
                # coverage falls through to ordinary recompute-resume.
                if self._kv_retain and self._kv_try_restore_resume(slot, req):
                    continue
                # Handoff fast path (engine_handoff.py, decode role):
                # a fresh page-aligned prompt whose pages AND shipped
                # logits are resident admits with ZERO prefill compute.
                if (
                    self._handoff_skip_covered
                    and not req.tokens
                    and self._spec_gamma == 0
                    and self._handoff_try_admit(slot, req)
                ):
                    continue
                # The EFFECTIVE prompt: original tokens plus anything a
                # previous occupancy already generated (recompute-resume
                # after preemption — empty for fresh requests, and always
                # empty under reserve admission).
                eff = req.prompt + req.tokens
                plen = len(eff)
                bucket = min(1 << (plen - 1).bit_length(), self.paged.max_len)
                if self._optimistic:
                    # Prompt pages + the first decode write (+ spec
                    # headroom); generation pages are allocated on demand
                    # by _ensure_frontier, preempting newer slots when
                    # the pool runs dry.
                    n_pages = math.ceil(
                        (plen + 1 + self._spec_gamma) / self.paged.page_size
                    )
                else:
                    # Reserve admission never preempts, so req.tokens is
                    # always empty here and plen == len(req.prompt): the
                    # worst-case chain, allocated up front.
                    n_pages = math.ceil(
                        (plen + req.max_new_tokens + self._spec_gamma)
                        / self.paged.page_size
                    )
                shared = (
                    self._match_prefix(
                        eff, bucket, burst_pages, req.adapter
                    )
                    if self.prefix_sharing
                    else []
                )
                # The trie walk continues into the host tier: consecutive
                # offloaded full pages past the device match are restored
                # into fresh pages below and counted as shared (the graft
                # never rewrites them — their rows are already the bytes
                # a recompute would write).
                host = (
                    self._kv_match_host(
                        eff, req.adapter, len(shared),
                        plen // self.paged.page_size,
                    )
                    if self.prefix_sharing and self._kv_retain
                    else []
                )
                n_private = n_pages - len(shared)
                if n_private > len(self.free_pages):
                    # Retained pages are one reclaim away from free:
                    # spill cold ones (LRU, leaf-first) before blocking.
                    # The protect set pins this request's own match — a
                    # matched-but-not-yet-referenced retained page must
                    # not be reclaimed out from under it.
                    self._kv_reclaim(
                        n_private - len(self.free_pages),
                        protect=frozenset(shared),
                    )
                if n_private > len(self.free_pages):
                    # FIFO: wait for pages rather than starving the head.
                    self._admit_page_blocked = True
                    break
                self.queue.popleft()
                req.admitted_at = time.monotonic()
                if not req.tokens:
                    # Fresh admission (preemption resumes re-enter via
                    # their own paths and already counted): observe the
                    # queue wait — the AIMD limiter's input signal, made
                    # scrapeable per priority class.
                    wait_s = req.admitted_at - req.submitted_at
                    if self.metrics:
                        self.metrics.queue_wait_seconds.observe(
                            wait_s, priority=PRIORITY_NAMES[req.priority]
                        )
                    if self.overload is not None:
                        self.overload.observe_admission(req, wait_s)
                # Refcounts and free-page moves stay under the lock too:
                # _update_gauges (called from submit() on another thread)
                # iterates _page_refs, and an unlocked resize here would
                # crash that iteration mid-scrape.
                private = [self.free_pages.popleft() for _ in range(n_private)]
                pages = shared + private
                for page in shared:
                    self._page_refs[page] += 1
                    if self._page_refs[page] == 1:
                        # 0 -> 1: the page came off the retained tier.
                        self._kv_revive(page)
                n_restored = len(host)
                if n_restored:
                    self._kv_restore_pages(
                        private[:n_restored], [e["rows"] for e in host]
                    )
                for page in private[n_restored:]:
                    # Ungrafted until _activate: shareable within this
                    # burst's same-bucket group only.  Restored pages are
                    # excluded — their content is already on device, so
                    # they are shareable immediately, like live pages.
                    burst_pages[page] = bucket
                    self._pending_pages.add(page)
                for page in private:
                    self._page_refs[page] = 1
                if self.prefix_sharing:
                    # Register this prompt's full pages (shared, restored,
                    # or fresh) as trie links so later same-prefix requests
                    # can ride them — including requests admitted in this
                    # SAME burst: a same-burst match is sound because every
                    # shared page's content is written by its first owner's
                    # graft before any decode step reads it.
                    self._register_prefix(
                        eff, pages, plen // self.paged.page_size, req.adapter
                    )
                self.slots[slot] = req
                self._slot_pages[slot] = pages
                self._slot_seq[slot] = self._seq_counter
                self._seq_counter += 1
                shared = pages[: len(shared) + n_restored]
            if self.spans:
                self.spans.record_span(
                    "pages.alloc",
                    req.trace_id,
                    start_monotonic=req.admitted_at,
                    parent_id=req.root_span,
                    attrs={
                        "rid": req.rid,
                        "pages": len(pages),
                        "shared": len(shared),
                    },
                )
            admitted.append((slot, req, pages, len(shared)))

        if (
            self._admit_page_blocked
            and not was_page_blocked
            and self.flight is not None
        ):
            # Edge-triggered (the gate re-trips every step while blocked;
            # one event per episode is the black-box-legible shape).
            with self._lock:
                qd, free = len(self.queue), len(self.free_pages)
            self.flight.record(
                "admission.page_blocked", queue_depth=qd, free_pages=free
            )
        if not admitted:
            return []
        # Group by length bucket; each group becomes ONE prefill job
        # (advanced chunk-by-chunk from step()).
        groups: dict[int, list[tuple[int, Request, list[int], int]]] = {}
        for item in admitted:
            plen = len(item[1].prompt) + len(item[1].tokens)
            bucket = min(1 << (plen - 1).bit_length(), self.paged.max_len)
            groups.setdefault(bucket, []).append(item)
        for items in groups.values():
            self._start_prefill(items)
        return []

    def _set_slot_sampler(self, slot: int, req: Request) -> None:
        """Install a request's sampler scalars on its slot.  A greedy
        slot's token is the argmax regardless of top_k/top_p, so they
        normalize to "off" — otherwise one greedy+top_k request would
        drag the whole batch onto the filtered (sorting) step path for
        zero output change.  Shared by activation and the kv-cache
        restore-resume path (which rebuilds a slot without a graft)."""
        if req.temperature > 0:
            topk = req.top_k if req.top_k is not None else self.cfg.vocab_size
            topp = req.top_p if req.top_p is not None else 1.0
        else:
            topk, topp = self.cfg.vocab_size, 1.0
        self._slot_temp[slot] = req.temperature
        self._slot_topk[slot] = topk
        self._slot_topp[slot] = topp
        if req.logit_bias:
            ids_l = list(req.logit_bias)
            vals_l = list(req.logit_bias.values())
            pad = self.MAX_BIAS - len(ids_l)
            self._slot_bias_ids[slot] = ids_l + [0] * pad
            self._slot_bias_vals[slot] = vals_l + [0.0] * pad
        else:
            self._slot_bias_ids[slot] = [0] * self.MAX_BIAS
            self._slot_bias_vals[slot] = [0.0] * self.MAX_BIAS
        self._slot_aid[slot] = req.adapter if req.adapter is not None else -1

    @in_phase("prefill.first_token")
    def _sample_first_token(self, req: Request, last_logits) -> int:
        """Sample one request's ADMISSION token from its last-position
        logits — the same math the jitted step applies (bias what gets
        picked, report unbiased logprobs, greedy ignores filters).
        Shared by prefill activation and the handoff no-prefill
        admission (engine_handoff.py), which samples from the logits
        the PREFILL replica shipped — same values, same schedule, so
        streams stay bit-identical across the split."""
        last_logits = jnp.asarray(last_logits)
        if req.logit_bias:
            ids = jnp.asarray(list(req.logit_bias), jnp.int32)
            vals = jnp.asarray(list(req.logit_bias.values()), jnp.float32)
            picked_logits = last_logits.at[ids].add(
                vals.astype(last_logits.dtype)
            )
        else:
            picked_logits = last_logits
        if req.temperature > 0:
            topk = req.top_k if req.top_k is not None else self.cfg.vocab_size
            topp = req.top_p if req.top_p is not None else 1.0
            self._rng, sub = jax.random.split(self._rng)
            filtered = filter_top_k_top_p(
                (picked_logits / req.temperature)[None, :],
                jnp.asarray([topk], jnp.int32),
                jnp.asarray([topp], jnp.float32),
            )
            first = int(jax.random.categorical(sub, filtered[0]))
        else:
            first = int(jnp.argmax(picked_logits))
        if req.logprobs:
            # Appended BEFORE the token so a streaming snapshot never
            # sees a token without its logprob.
            req.token_logprobs.append(
                float(
                    _token_logprob(
                        last_logits[None, :],
                        jnp.asarray([first], jnp.int32),
                    )[0]
                )
            )
        return first

    def _activate(self, job: dict) -> list[Request]:
        """Graft a completed prefill job's K/V into pages, sample each
        request's first token, and mark the slots ready to decode."""
        # Graft/sample dispatches can hit fresh page-count shapes: grace
        # the hung-step deadline for this admission step.
        self._wd_grace("activate")
        finished: list[Request] = []
        for row_idx, (slot, req, pages, n_shared) in enumerate(job["items"]):
            # Effective length: a resumed request's prefill covered its
            # original prompt plus the tokens generated before eviction
            # (req.tokens grows below AFTER this is read).
            resumed = bool(req.tokens)
            plen = len(req.prompt) + len(req.tokens)
            self._graft(
                slot, job["cache"], pages, plen, n_shared, row_idx=row_idx
            )
            # Grafted: the private pages are now real K/V and may be
            # prefix-shared by any later request.  The pending->grafted
            # transition changes what the fabric digest may advertise
            # (it must skip pending pages), so it has to invalidate the
            # version-keyed digest cache like any trie edit — otherwise
            # a digest built mid-prefill stays cached as empty forever.
            grafted = self._pending_pages.intersection(pages[n_shared:])
            if grafted:
                self._pending_pages.difference_update(grafted)
                with self._lock:
                    self._trie_version += 1
            first = self._sample_first_token(req, job["logits"][row_idx])
            req.tokens.append(first)
            self._slot_last[slot] = first
            self._slot_len[slot] = plen
            self._set_slot_sampler(slot, req)
            self._slot_ready[slot] = True
            if resumed:
                # Preemption-resume accounting, recompute flavor: the
                # whole effective prompt re-ran through prefill (the
                # restore path — engine_kvcache._kv_try_restore_resume —
                # records its zero-recompute counterpart; together the
                # two say whether victims actually got back in and what
                # their second admission cost).
                self.kv_resumes_recompute += 1
                self.kv_resume_recomputed_tokens += plen
                if self.metrics:
                    self.metrics.resumes.inc(mode="recompute")
                    self.metrics.resume_recomputed_tokens.inc(plen)
                if self.flight is not None:
                    self.flight.record(
                        "engine.resume",
                        rid=req.rid,
                        mode="recompute",
                        restored_tokens=0,
                        recomputed_tokens=plen,
                        pages_shared=n_shared,
                    )
            now = time.monotonic()
            # First emitted token: the TTFT/ITL anchor for this slot.
            req.first_token_at = now
            self._slot_emit_t[slot] = now
            self._step_tokens += 1  # the admission token counts (profiler)
            if self.metrics:
                # A preemption resume re-activates the SAME client
                # request: counting it again would skew requests_total
                # exactly in the overload regime it helps diagnose.
                if not resumed:
                    self.metrics.requests.inc()
                    self.metrics.wait_seconds.observe(now - req.submitted_at)
                    self.metrics.ttft_seconds.observe(now - req.submitted_at)
                self.metrics.tokens.inc()
            if not resumed and self.anomaly is not None:
                # A sustained TTFT blow-up (queue wait, prefill stall)
                # becomes an incident record with the flight window of
                # what the engine was doing attached.
                self.anomaly.observe(
                    "engine.ttft_seconds", now - req.submitted_at
                )
            if self.spans and not resumed:
                # Queue wait and prefill recorded post-hoc from the
                # lifecycle stamps, nested under the request root (a
                # resume re-runs prefill for the SAME client request:
                # its spans would duplicate the trio, so resumes only
                # annotate the root via the preemptions counter).
                self.spans.record_span(
                    "queue",
                    req.trace_id,
                    start_monotonic=req.submitted_at,
                    end_monotonic=req.admitted_at,
                    parent_id=req.root_span,
                    attrs={
                        "rid": req.rid,
                        # The limiter's input, per request: grep-able
                        # next to the tpu_engine_queue_wait_seconds
                        # histogram it aggregates into.
                        "wait_s": round(
                            req.admitted_at - req.submitted_at, 6
                        ),
                    },
                )
                self.spans.record_span(
                    "prefill",
                    req.trace_id,
                    start_monotonic=req.admitted_at,
                    end_monotonic=now,
                    parent_id=req.root_span,
                    attrs={
                        "rid": req.rid,
                        "prompt_tokens": plen,
                        "bucket": job["bucket"],
                        "batched_with": len(job["items"]) - 1,
                    },
                )
            self._maybe_finish(slot)
            if req.done:
                finished.append(req)
        # The job's routing counts: its chunks are done (the first tokens
        # above were sampled from their logits), so this waits for nothing.
        for stats in job["moe_stats"]:
            self._moe_fold("prefill", np.asarray(stats).astype(np.int64))
        # Activated slots carry fresh scalars (last token, length, sampler
        # settings, adapter): rebuild the device step state (engine.py).
        self._mark_state_dirty()
        return finished

    @staticmethod
    def _hit_stop(req: Request) -> bool:
        """True when the output's tail equals one of the request's stop
        sequences (or already did): truncates the matched suffix (and its
        logprobs) and LATCHES ``req.stopped`` — the evidence is deleted,
        so the flag carries the verdict to _maybe_finish."""
        if req.stopped:
            return True
        if not req.stop:
            return False
        for seq in req.stop:
            n = len(seq)
            if n and len(req.tokens) >= n and req.tokens[-n:] == seq:
                del req.tokens[-n:]
                if req.logprobs:
                    del req.token_logprobs[len(req.tokens):]
                req.stopped = True
                return True
        return False

    def _slo_observe_finish(self, req, now: float, slot=None):
        """SLI verdicts + tenant usage at the end of a request's life
        (utils/slo.py; no-op when the SLO plane is off).

        Called under the engine lock from every terminal path: ordinary
        finish (_maybe_finish, BEFORE the slot tears down so the page
        count is still live), the expired-queue shed sweep (those
        requests never pass through _maybe_finish), and — via
        _slo_observe_submit_shed — the submit-side shed gate.  Verdict
        rules: a shed is an availability failure; a client cancel is
        EXCLUDED from every objective (the service didn't fail, the
        client left); latency objectives score only requests that
        actually emitted tokens."""
        if self.slo is None:
            return
        if req.shed is not None:
            self._slo_emit("availability", False)
        elif not req.cancelled:
            self._slo_emit("availability", True)
            if req.tokens and req.first_token_at > 0.0:
                ttft = self.slo.objectives.get("ttft")
                if ttft is not None and ttft.threshold_s is not None:
                    self._slo_emit(
                        "ttft",
                        req.first_token_at - req.submitted_at
                        <= ttft.threshold_s,
                    )
                itl = self.slo.objectives.get("itl_p99")
                if (
                    itl is not None
                    and itl.threshold_s is not None
                    and req.itl_peak_s > 0.0
                ):
                    self._slo_emit("itl_p99", req.itl_peak_s <= itl.threshold_s)
        if self.usage is not None:
            admitted = req.admitted_at > 0.0
            queue_wait = max(
                0.0, (req.admitted_at if admitted else now) - req.submitted_at
            )
            pages = 0
            if slot is not None:
                # Logical pages covering the sequence (shared prefix
                # included): page-seconds as a conservative upper bound.
                pages = self._slot_page_base[slot] + len(
                    self._slot_pages[slot]
                )
            kv_page_s = (
                pages * max(0.0, now - req.admitted_at) if admitted else 0.0
            )
            label = self.usage.record_request(
                req.tenant,
                prompt_tokens=len(req.prompt) if admitted else 0,
                decode_tokens=len(req.tokens),
                kv_page_seconds=kv_page_s,
                queue_wait_seconds=queue_wait,
            )
            if self.metrics:
                m = self.metrics
                m.tenant_requests.inc(tenant=label)
                if admitted and req.prompt:
                    m.tenant_prompt_tokens.inc(len(req.prompt), tenant=label)
                if req.tokens:
                    m.tenant_decode_tokens.inc(len(req.tokens), tenant=label)
                if kv_page_s > 0.0:
                    m.tenant_kv_page_seconds.inc(kv_page_s, tenant=label)
                if queue_wait > 0.0:
                    m.tenant_queue_wait_seconds.inc(queue_wait, tenant=label)

    def _slo_emit(self, objective: str, good: bool):
        self.slo.record(objective, good)
        if self.metrics:
            self.metrics.sli_events.inc(
                objective=objective, verdict="good" if good else "bad"
            )

    def _slo_observe_submit_shed(self, tenant: str):
        """A submit-side shed never creates a Request, but the client
        still saw a failure: one bad availability verdict, one metered
        (empty) usage row."""
        if self.slo is None:
            return
        self._slo_emit("availability", False)
        if self.usage is not None:
            label = self.usage.record_request(tenant)
            if self.metrics:
                self.metrics.tenant_requests.inc(tenant=label)

    def observe_submit_shed(self, tenant: str = ""):
        """Public hook for door sheds that never reach submit() — the
        HTTP layer's deadline<=0 fail-fast 504.  The client saw a
        failure, so the SLO plane scores it like any submit-side shed;
        without this, a fleet could burn its availability budget on
        door sheds invisibly."""
        tenant = str(tenant or "")[: self.MAX_TENANT_LEN]
        with self._lock:
            self._slo_observe_submit_shed(tenant)

    def _maybe_finish(self, slot: int):
        req = self.slots[slot]
        if req is None:
            return
        if (
            req.cancelled
            or len(req.tokens) >= req.max_new_tokens
            or (
                self.eos_id is not None
                and req.tokens
                and req.tokens[-1] == self.eos_id
            )
            or self._hit_stop(req)
        ):
            req.done = True
            req.finished_at = time.monotonic()
            if self.overload is not None:
                self.overload.on_finish(req)
            # SLO verdicts + tenant usage ride the same span-outcome
            # seam, BEFORE _clear_slot so the page count is still live.
            self._slo_observe_finish(req, req.finished_at, slot=slot)
            if (
                self.metrics
                and req.tokens
                and req.shed is None
                and not req.cancelled
                and (req.deadline is None or req.finished_at <= req.deadline)
            ):
                # Goodput: tokens a client will actually use — completed
                # in-deadline work (deadline-free requests count on
                # completion).  tokens_total minus this is burned work.
                self.metrics.goodput_tokens.inc(len(req.tokens))
            if self.spans:
                # The decode child covers first token -> finish; the root
                # closes the trace with the whole-request wall time and
                # the outcome, under the span id reserved at submit.
                self.spans.record_span(
                    "decode",
                    req.trace_id,
                    start_monotonic=req.first_token_at or req.finished_at,
                    end_monotonic=req.finished_at,
                    parent_id=req.root_span,
                    attrs={"rid": req.rid, "tokens": len(req.tokens)},
                )
                root_attrs = {
                    "rid": req.rid,
                    "prompt_tokens": len(req.prompt),
                    "new_tokens": len(req.tokens),
                    "outcome": f"shed:{req.shed}"
                    if req.shed
                    else (
                        "cancelled"
                        if req.cancelled
                        else ("stopped" if req.stopped else "completed")
                    ),
                }
                if req.trace_parent:
                    # Cross-process link (X-Trace-Context): the router
                    # attempt span this tree roots under — the join key
                    # tools/trace_assemble.py resolves fleet-wide.
                    root_attrs["parent"] = req.trace_parent
                    root_attrs["hop"] = req.trace_hop
                    root_attrs["attempt"] = req.trace_attempt
                self.spans.record_span(
                    "request",
                    req.trace_id,
                    start_monotonic=req.submitted_at,
                    end_monotonic=req.finished_at,
                    span_id=req.root_span,
                    attrs=root_attrs,
                )
            self._clear_slot(slot)
