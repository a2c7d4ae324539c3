"""Disaggregated prefill/decode serving: engine roles + KV-page handoff.

Long-prompt prefill and interactive decode fight for the same step
loop: one 8k-token admission stalls every active slot for the duration
of its chunked prefill, so a prefill burst inflates decode ITL p99
fleet-wide.  This module splits the engine into ROLES and moves the
finished KV pages between them over a per-request wire stream:

- **Roles** (``ServingEngine(role=...)``, CLI ``--role``):

  - ``unified`` (default) — today's engine, byte-for-byte: prefills and
    decodes in one loop, ignores every handoff surface.
  - ``prefill`` — runs chunked prefill to completion for ``POST
    /v1/prefill`` probes, publishes each finished FULL page into the
    content-addressed :class:`~.engine_kvcache.HostKVArena` keyed by
    cumulative token prefix, and streams the entries to the caller as
    each chunk lands — it emits no decode tokens (``/generate`` answers
    409) and never runs a decode step for handoff work (the probe's
    single admission token comes from the prefill pass's own logits).
  - ``decode`` — admits a request whose full-page prefix is already
    RESIDENT (live/retained trie pages or host-arena entries — the
    restore path then rebuilds the pages with one ``.at[pages].set``
    per pool per layer and the prefill pass SKIPS every covered chunk),
    pulls a non-resident prefix from the prefill replica named by the
    router's ``X-Handoff-Source`` header, and refuses (409 +
    ``X-Prefill-Needed``) one that is neither resident nor fetchable.

- **Wire protocol** (``POST /v1/prefill``): a per-request variant of
  the PR 14 snapshot stream — the SAME ``MAGIC | version | header |
  entries`` encoding (engine_snapshot.encode_preamble/encode_entry:
  per-entry CRC32, full layout compare, entry count in the header), so
  the decode side parses it through the SAME verifier the disk and
  peer-snapshot paths use.  The entry count (the prompt's full-page
  count) is known before any compute, so the preamble goes out first
  and each entry streams the moment its chunk's K/V exist in the
  prefill job's carried dense cache — transfer overlaps prefill
  compute instead of following it.

- **Degradation contract** (pinned in tier-1, scored under chaos): the
  decode side parses BEFORE admitting, so a prefill replica dying
  mid-transfer, a torn stream, or an incompatible peer admit NOTHING —
  the request falls back to ordinary LOCAL prefill (the unified path),
  never a poisoned cache, never a dropped stream.  A fleet with no
  healthy prefill pool degrades to unified dispatch at the router
  (router/disagg.py) — zero new failure modes for short chat traffic.

Failpoint sites (docs/chaos.md): ``engine.handoff.serve`` (``error``
refuses the probe with 503, ``truncate[:fraction]`` tears the stream
after a fraction of the entries — the prefill-died-mid-transfer shape)
and ``engine.handoff.fetch`` (``error`` = dial failure on the decode
side, ``truncate[:fraction]`` reads a prefix of the bytes).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Optional

import numpy as np

from ..utils import failpoints
from ..utils.prefixbloom import PrefixBloom
from . import engine_snapshot as snap

ROLES = ("unified", "prefill", "decode")
# tpu_engine_role gauge values (bounded, documented in operations.md).
ROLE_VALUES = {"unified": 0, "prefill": 1, "decode": 2}

PREFILL_ROUTE = "/v1/prefill"
# Optional trailing wire section: the prefill side's LAST-position
# logits (the values its own activation would sample the admission
# token from).  With them, a decode replica admits a fully-covered
# page-aligned prompt with ZERO prefill compute — restore pages, sample
# locally from the shipped logits (same values, same sampler math →
# bit-identical streams).  Absent or torn, the decode side falls back
# to the seeded-tail-chunk path; entries already verified stay good.
LOGITS_MAGIC = b"TPUHOLG1"
# Router -> decode replica: the prefill replica to pull a non-resident
# prefix from ("host:port" — the handoff locator), or the LOCAL
# sentinel ("run the prefill yourself": the router classified the
# prompt short, or the prefill pool is down — the unified degradation).
HANDOFF_SOURCE_HEADER = "X-Handoff-Source"
HANDOFF_LOCAL = "local"
# Decode replica -> caller on a 409 refusal: how many full prefix pages
# are missing (the router's signal that the request needs a prefill
# dispatch, not another decode replica).
PREFILL_NEEDED_HEADER = "X-Prefill-Needed"
# Fabric pull discipline: when this header rides a /v1/prefill request,
# the serving side streams RESIDENT pages only and answers 409 when
# coverage is incomplete — it never runs a prefill probe for the
# caller.  The router's fabric locator stamps it on every any-peer
# pull, so a bloom false positive or a stale advertisement costs one
# refused dial and the puller degrades to LOCAL prefill; the classic
# prefill-pool pull omits it and keeps the probe-on-miss contract.
FABRIC_RESIDENT_ONLY_HEADER = "X-Fabric-Resident-Only"


class HandoffTap:
    """One in-flight prefill probe's entry stream, filled by the engine
    OWNER thread as chunks complete and drained by the ``/v1/prefill``
    handler thread.

    The owner thread reads each newly covered full page's rows out of
    the probe job's carried dense cache (safe: it runs between chunk
    dispatches, never concurrent with the donation), publishes them
    into the host arena, and pushes the encoded-entry ingredients here;
    the handler blocks on :meth:`pop` and writes them to the socket.
    ``_cond`` guards ``_ready``/``pushed`` (its own leaf lock — the
    handler must be able to block without holding the engine lock)."""

    def __init__(self, req, prompt: list, adapter: Optional[int], n_full: int):
        self.req = req
        self.prompt = list(prompt)
        self.adapter = adapter
        self.n_full = n_full
        self.pushed = 0  # pages fed by the owner so far; guarded by: _cond
        # Last-position logits once their chunk computed (owner writes
        # once, handler reads after the final entry — plain store/load).
        self.logits: Optional[np.ndarray] = None
        self._ready: deque = deque()  # guarded by: _cond
        self._cond = threading.Condition()

    def push(self, key: tuple, rows: dict) -> None:
        with self._cond:
            self._ready.append((key, rows))
            self.pushed += 1
            self._cond.notify_all()

    def pop(self, timeout: float) -> Optional[tuple]:
        with self._cond:
            if not self._ready:
                self._cond.wait(timeout)
            if not self._ready:
                return None
            return self._ready.popleft()

    @property
    def dead(self) -> bool:
        """The probe finished (or was shed/cancelled) — if pages are
        still missing past this point, they are never coming."""
        return bool(self.req.done)


class HandoffMixin:
    """Role bookkeeping + the prefill-side tap feed, mixed into
    ServingEngine like the other engine_* files."""

    def _validate_role(self, role: str) -> None:
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        if role != "unified" and self.slot_state_bytes:
            # A handoff ships pages; the decode side would skip the
            # chunks that build the recurrent state (models/ssm.py).
            raise ValueError(
                f"role={role!r} is not supported on a model with per-slot "
                "recurrent state (cfg.mixer): a prefill handoff carries "
                "K/V pages, not the state the skipped chunks would build"
            )
        if role != "unified" and self.cfg.mla is not None:
            raise ValueError(
                f"role={role!r} is not supported with latent attention or expert "
                "layers (cfg.mla, cfg.moe): a prefill handoff is not tried on a "
                "latent pool, and the chunks it skips would lose their routing counts"
            )
        if role != "unified":
            # Both split roles live on the content-addressed KV tiers:
            # the prefill role PUBLISHES into the arena and serves from
            # the retained tier; the decode role admits by restoring
            # from them.  Refusing here beats a replica that silently
            # recomputes everything it was deployed to avoid.
            if not self.prefix_sharing:
                raise ValueError(f"role={role!r} requires prefix_sharing")
            if not self._kv_retain:
                raise ValueError(f"role={role!r} requires kv_retain")
            if not self._kv_arena.enabled:
                raise ValueError(
                    f"role={role!r} requires kv_host_cache_mb > 0 (the "
                    "content-addressed arena is the handoff medium)"
                )

    def _init_handoff(self, role: str) -> None:
        self._validate_role(role)
        self.role = role
        # Decode-role engines SKIP prefill chunks whose positions are
        # fully covered by restored/shared pages (the dense cache is
        # seeded from those pages instead — engine_admission
        # _start_prefill); unified engines keep the exact historical
        # prefill schedule, so nothing changes for existing traffic.
        self._handoff_skip_covered = role == "decode"
        self._handoff_taps: dict[int, HandoffTap] = {}  # guarded by: _lock
        # Host-visible counters (exported via metrics when wired and
        # through handoff_state / GET /debug/disagg).
        self.handoff_serves = 0
        self.handoff_fetches = 0
        self.handoff_fetch_failures = 0
        self.handoff_published_entries = 0
        self.handoff_served_entries = 0
        self.handoff_fetched_entries = 0
        self.handoff_refusals = 0
        self.handoff_skipped_tokens = 0  # prefill positions never computed
        self.handoff_noprefill_admits = 0  # zero-compute admissions
        # Fleet KV fabric: cached bloom advertisement of the prefixes
        # this replica can serve over /v1/prefill, rebuilt only when
        # the arena or trie actually mutated (version pair below), so
        # the router's ?summary=1 poll stays cheap.
        self._fabric_digest_wire: Optional[dict] = None  # guarded by: _lock
        self._fabric_digest_versions = (-1, -1)  # guarded by: _lock
        # Single-flight fabric pulls, keyed by source replica: a burst
        # of requests all missing the same shared prefix collapses to
        # ONE wire pull — the winner dials, the rest wait on its Event
        # and then ride whatever it admitted (http_server admission
        # gate).  Guarded by: _lock.
        self._handoff_pull_waits: dict = {}
        self.fabric_digest_builds = 0
        self.fabric_pulls = 0
        self.fabric_pull_failures = 0
        self.fabric_drops = 0
        if self.metrics:
            self.metrics.role.set(ROLE_VALUES[role])

    def set_role(self, role: str) -> bool:
        """Runtime role flip (the fleet controller's rebalancing verb,
        ``POST /debug/role``): same preconditions as construction —
        both split roles need the content-addressed KV tiers.  In-flight
        work is untouched: queued/slotted requests finish under the old
        contract, and the new role governs admission from the next
        request on (a flipped-to-prefill replica starts answering 409
        on /generate; the router lifts it off the ring at its next
        summary poll).  Idempotent — returns False when already there."""
        self._validate_role(role)
        with self._lock:
            if role == self.role:
                return False
            previous = self.role
            self.role = role
            self._handoff_skip_covered = role == "decode"
        if self.metrics:
            self.metrics.role.set(ROLE_VALUES[role])
        self.flight.record(
            "engine.role_changed", previous=previous, role=role
        )
        return True

    # ------------------------------------------------------ prefill side

    def handoff_begin(self, prompt: list, adapter: Optional[int]) -> HandoffTap:
        """Start one prefill probe for ``/v1/prefill``: submit the
        prompt with ``max_new_tokens=1`` (it finishes AT activation —
        the engine never dispatches a decode step for it) and register
        a tap the owner thread feeds as chunks complete.  Raises
        whatever ``submit`` raises (validation, overload shed)."""
        req = self.submit(list(prompt), 1, adapter=adapter)
        tap = HandoffTap(
            req, prompt, adapter, len(prompt) // self.paged.page_size
        )
        with self._lock:
            self._handoff_taps[req.rid] = tap
        return tap

    def handoff_end(self, tap: HandoffTap) -> None:
        with self._lock:
            self._handoff_taps.pop(tap.req.rid, None)
        if not tap.req.done:
            self.cancel(tap.req)

    def _handoff_feed(self, job: dict) -> None:
        """Owner-thread hook after one prefill-chunk advance
        (engine_admission._advance_prefill): for every tapped request in
        the job, read the newly covered FULL pages' rows out of the
        carried dense cache, publish them into the host arena (the
        content-addressed "finished pages" store), and push them to the
        tap's handler.  Zero cost without taps (one dict check at the
        call site)."""
        ps = self.paged.page_size
        for row_idx, (slot, req, pages, n_shared) in enumerate(job["items"]):
            tap = self._handoff_taps.get(req.rid)
            if tap is None:
                continue
            plen = len(req.prompt) + len(req.tokens)
            covered = min(job["pos"], plen) // ps
            if tap.logits is None and job["logits"][row_idx] is not None:
                # Capture BEFORE pushing this feed's entries: the
                # handler streams the logits section right after the
                # final entry, so the store must happen-before the
                # final push.
                tap.logits = np.asarray(job["logits"][row_idx])
                with self._lock:
                    self._kv_arena.put(
                        ("logits", self._trie_root(tap.adapter),
                         tuple(tap.prompt)),
                        {"logits": tap.logits},
                        tap.logits.nbytes,
                    )
            for i in range(tap.pushed, min(covered, tap.n_full)):
                rows: dict[str, dict[str, np.ndarray]] = {}
                for name in self._layer_names:
                    att = self.cache[name]["attn"]
                    src = job["cache"][name]["attn"]
                    rows[name] = {
                        pool: np.asarray(
                            src["cached_" + pool[len("pool_"):]][
                                row_idx, i * ps : (i + 1) * ps
                            ]
                        )
                        for pool in self._kv_pool_names(att)
                    }
                key = (
                    "prefix",
                    self._trie_root(tap.adapter),
                    tuple(tap.prompt[: (i + 1) * ps]),
                )
                with self._lock:
                    self._kv_arena.put(key, {"rows": rows},
                                       self._kv_rows_nbytes(rows))
                    self.handoff_published_entries += 1
                if self.metrics:
                    self.metrics.handoff_entries.inc(direction="published")
                tap.push(key, rows)
            if (
                tap.pushed >= tap.n_full
                and self.flight is not None
                and tap.n_full
            ):
                self.flight.record(
                    "handoff.published",
                    rid=req.rid,
                    entries=tap.n_full,
                    prompt_tokens=plen,
                )

    def handoff_resident_entries(
        self, prompt: list, adapter: Optional[int]
    ) -> Optional[list[tuple[tuple, dict]]]:
        """Every full prefix page of ``prompt`` as ``(key, rows)``
        entries read from the tiers — the no-compute serve path for a
        prefix a probe (or earlier traffic) already published.  None
        when coverage is incomplete (the caller runs a probe instead)."""
        ps = self.paged.page_size
        n_full = len(prompt) // ps
        root = self._trie_root(adapter)
        out: list[tuple[tuple, dict]] = []
        with self._lock:
            parent = root
            for i in range(n_full):
                key = ("prefix", root, tuple(prompt[: (i + 1) * ps]))
                page = (
                    self._prefix_pages.get(
                        (parent, tuple(prompt[i * ps : (i + 1) * ps]))
                    )
                    if parent is not None
                    else None
                )
                if page is not None and page not in self._pending_pages:
                    out.append((key, self._kv_read_page_rows(page)))
                    parent = page
                    continue
                parent = None  # device chain broken: arena-only from here
                entry = self._kv_arena.get(key)
                if entry is None:
                    return None
                out.append((key, entry["rows"]))
        return out

    def handoff_resident_prefix_entries(
        self, prompt: list, adapter: Optional[int]
    ) -> list[tuple[tuple, dict]]:
        """The LEADING resident full pages of ``prompt`` as ``(key,
        rows)`` entries — the fabric any-peer serve: a peer sharing
        only a prefix of this prompt (the fleet-wide shared system
        prompt) pulls exactly the pages this replica holds, and a
        bloom false positive overclaiming depth just serves shallower.
        Empty when not even the first page is resident (the caller
        answers the resident-only 409; never a probe)."""
        ps = self.paged.page_size
        n_full = len(prompt) // ps
        root = self._trie_root(adapter)
        out: list[tuple[tuple, dict]] = []
        with self._lock:
            parent = root
            for i in range(n_full):
                key = ("prefix", root, tuple(prompt[: (i + 1) * ps]))
                page = (
                    self._prefix_pages.get(
                        (parent, tuple(prompt[i * ps : (i + 1) * ps]))
                    )
                    if parent is not None
                    else None
                )
                if page is not None and page not in self._pending_pages:
                    out.append((key, self._kv_read_page_rows(page)))
                    parent = page
                    continue
                parent = None  # device chain broken: arena-only from here
                entry = self._kv_arena.get(key)
                if entry is None:
                    break
                out.append((key, entry["rows"]))
        return out

    # ------------------------------------------------------- decode side

    def handoff_coverage(
        self, prompt: list, adapter: Optional[int]
    ) -> tuple[int, int]:
        """(covered, n_full): how many of the prompt's leading FULL
        pages are resident — a live/retained trie chain from the start,
        continued content-addressed into the host arena (exactly the
        coverage the admission walk will find).  The decode-role
        admission gate."""
        ps = self.paged.page_size
        n_full = len(prompt) // ps
        root = self._trie_root(adapter)
        covered = 0
        with self._lock:
            parent = root
            for i in range(n_full):
                page = self._prefix_pages.get(
                    (parent, tuple(prompt[i * ps : (i + 1) * ps]))
                )
                if page is None or page in self._pending_pages:
                    break
                parent = page
                covered += 1
            for i in range(covered, n_full):
                if ("prefix", root, tuple(prompt[: (i + 1) * ps])) not in (
                    self._kv_arena
                ):
                    break
                covered += 1
        return covered, n_full

    def _handoff_try_admit(self, slot: int, req) -> bool:
        """Decode-role admission FAST PATH for a fresh handed-off
        request: when the prompt is page-aligned, every full page is
        resident (live/retained/arena), and the prefill side's
        last-position logits were shipped, rebuild the slot with ZERO
        prefill compute — restore the pages, sample the admission token
        locally from the shipped logits (the same values + sampler math
        activation uses, so streams stay bit-identical across the
        split), and mark the slot ready to decode.  Anything short
        returns False and the ordinary admission runs (the covered
        chunks still skip via the seeded dense cache).  Caller holds
        the lock; mirrors ``_kv_try_restore_resume``'s discipline."""
        ps = self.paged.page_size
        eff = req.prompt
        plen = len(eff)
        if plen % ps or plen == 0:
            return False
        n_full = plen // ps
        root = self._trie_root(req.adapter)
        lg = self._kv_arena.get(("logits", root, tuple(eff)))
        if lg is None:
            return False
        import math
        import time as time_mod

        bucket = min(1 << (plen - 1).bit_length(), self.paged.max_len)
        shared = (
            self._match_prefix(eff, bucket, {}, req.adapter)[:n_full]
            if self.prefix_sharing
            else []
        )
        host = self._kv_match_host(eff, req.adapter, len(shared), n_full)
        if len(shared) + len(host) < n_full:
            return False
        if self._optimistic:
            n_pages = math.ceil((plen + 1 + self._spec_gamma) / ps)
        else:
            n_pages = math.ceil(
                (plen + req.max_new_tokens + self._spec_gamma) / ps
            )
        n_private = n_pages - len(shared)
        if n_private > len(self.free_pages):
            self._kv_reclaim(
                n_private - len(self.free_pages), protect=frozenset(shared)
            )
        if n_private > len(self.free_pages):
            return False  # pool-blocked: stay queued like any head
        self.queue.popleft()
        req.admitted_at = time_mod.monotonic()
        wait_s = req.admitted_at - req.submitted_at
        if self.metrics:
            from .engine_overload import PRIORITY_NAMES

            self.metrics.queue_wait_seconds.observe(
                wait_s, priority=PRIORITY_NAMES[req.priority]
            )
        if self.overload is not None:
            self.overload.observe_admission(req, wait_s)
        private = [self.free_pages.popleft() for _ in range(n_private)]
        pages = shared + private
        for page in shared:
            self._page_refs[page] += 1
            if self._page_refs[page] == 1:
                self._kv_revive(page)
        for page in private:
            self._page_refs[page] = 1
        if host:
            self._kv_restore_pages(
                private[: len(host)], [e["rows"] for e in host]
            )
        if self.prefix_sharing:
            self._register_prefix(eff, pages, n_full, req.adapter)

        first = self._sample_first_token(req, lg["logits"])
        req.tokens.append(first)

        # Slot state: the _graft/_activate table discipline without a
        # graft (every row is already in place) — as in
        # _kv_try_restore_resume.
        self._set_slot_row(slot, plen, pages)
        self.slots[slot] = req
        self._slot_pages[slot] = pages
        self._slot_page_base[slot] = 0
        self._slot_len[slot] = plen
        self._slot_last[slot] = first
        self._slot_seq[slot] = self._seq_counter
        self._seq_counter += 1
        self._set_slot_sampler(slot, req)
        self._slot_ready[slot] = True

        now = time_mod.monotonic()
        req.first_token_at = now
        self._slot_emit_t[slot] = now
        self._step_tokens += 1
        self.handoff_noprefill_admits += 1
        self.handoff_skipped_tokens += plen
        if self.metrics:
            self.metrics.requests.inc()
            self.metrics.wait_seconds.observe(now - req.submitted_at)
            self.metrics.ttft_seconds.observe(now - req.submitted_at)
            self.metrics.tokens.inc()
        if self.anomaly is not None:
            self.anomaly.observe(
                "engine.ttft_seconds", now - req.submitted_at
            )
        if self.flight is not None:
            self.flight.record(
                "handoff.admitted",
                rid=req.rid,
                prompt_tokens=plen,
                pages_shared=len(shared),
                pages_restored=len(host),
            )
        if self.spans:
            self.spans.record_span(
                "queue",
                req.trace_id,
                start_monotonic=req.submitted_at,
                end_monotonic=req.admitted_at,
                parent_id=req.root_span,
                attrs={"rid": req.rid, "wait_s": round(wait_s, 6)},
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
                    "bucket": 0,  # no prefill ran: the handoff covered it
                    "batched_with": 0,
                },
            )
        self._maybe_finish(slot)
        self._mark_state_dirty()
        self._update_gauges()
        return True

    def handoff_state(self) -> dict:
        """JSON-safe disaggregation snapshot: the body of
        ``GET /debug/disagg`` and the ``disagg`` block callers embed."""
        with self._lock:
            return {
                "role": self.role,
                "skip_covered_prefill": self._handoff_skip_covered,
                "taps_active": len(self._handoff_taps),
                "serves": self.handoff_serves,
                "served_entries": self.handoff_served_entries,
                "published_entries": self.handoff_published_entries,
                "fetches": self.handoff_fetches,
                "fetch_failures": self.handoff_fetch_failures,
                "fetched_entries": self.handoff_fetched_entries,
                "refusals": self.handoff_refusals,
                "skipped_prefill_tokens": self.handoff_skipped_tokens,
                "noprefill_admits": self.handoff_noprefill_admits,
            }

    # ------------------------------------------------------- fleet fabric

    def fabric_digest(self) -> Optional[dict]:
        """Wire-form bloom advertisement (utils/prefixbloom.py) of every
        cumulative full-page prefix this replica can serve over ``POST
        /v1/prefill`` — grafted/retained trie chains walked from the
        roots plus the host arena's offloaded entries, i.e. exactly the
        coverage :meth:`handoff_resident_entries` would find.  ``None``
        when the replica cannot serve pulls at all (prefix sharing or
        the arena off) — the router then never places prefixes here.

        Rides the ``?summary=1`` poll, so the fast path is lock-free by
        the summary handler's documented racy-read contract: the cached
        dict and its (arena, trie) version pair are read off-lock, and
        a torn read costs at worst one redundant rebuild or one poll
        tick of staleness — staleness is already survivable fabric-wide
        (a stale advertisement degrades to a refused pull and local
        prefill).  The rebuild itself runs under the lock."""
        if not self.prefix_sharing or not self._kv_arena.enabled:
            return None
        cached = self._fabric_digest_wire
        if cached is not None and self._fabric_digest_versions == (
            self._kv_arena.version,
            self._trie_version,
        ):
            return cached
        with self._lock:
            versions = (self._kv_arena.version, self._trie_version)
            if (
                self._fabric_digest_wire is not None
                and self._fabric_digest_versions == versions
            ):
                return self._fabric_digest_wire
            bloom = PrefixBloom()
            seen: set = set()
            for key in self._kv_arena.prefix_keys():
                ident = (key[1], key[2])
                if ident not in seen:
                    seen.add(ident)
                    bloom.add(key[1], key[2])
            # Trie-resident chains: group links by parent, BFS from the
            # pseudo-roots (negative parents) accumulating cumulative
            # token tuples — O(resident pages).  Pending pages are the
            # un-grafted prefill frontier; resident_entries refuses
            # them, so the digest must not advertise them either.
            children: dict[int, list[tuple[tuple, int]]] = {}
            for (parent, chunk), page in self._prefix_pages.items():
                children.setdefault(parent, []).append((chunk, page))
            stack = [(root, (), root) for root in children if root < 0]
            while stack:
                parent, cum, root = stack.pop()
                for chunk, page in children.get(parent, ()):
                    if page in self._pending_pages:
                        continue
                    tokens = cum + chunk
                    ident = (root, tokens)
                    if ident not in seen:
                        seen.add(ident)
                        bloom.add(root, tokens)
                    stack.append((page, tokens, root))
            wire = bloom.to_wire()
            wire["page_size"] = self.paged.page_size
            self._fabric_digest_wire = wire
            self._fabric_digest_versions = versions
            self.fabric_digest_builds += 1
            if self.metrics:
                self.metrics.fabric_digest_roots.set(len(seen))
            return wire

    def fabric_pull(
        self,
        source: str,
        prompt: list,
        adapter: Optional[int] = None,
        timeout_s: float = 30.0,
    ) -> dict:
        """Router-driven replication pull (``POST /debug/fabric/pull``):
        copy ``prompt``'s covered pages from ``source`` into this
        replica's arena through the SAME parse-before-admit verifier as
        a request-path fetch — a dead peer or torn stream admits
        nothing and this replica simply stays a non-owner."""
        result = fetch_prefill(
            self,
            source,
            prompt,
            adapter=adapter,
            timeout_s=timeout_s,
            resident_only=True,
        )
        ok = bool(result.get("ok"))
        with self._lock:
            if ok:
                self.fabric_pulls += 1
            else:
                self.fabric_pull_failures += 1
        if self.metrics:
            self.metrics.fabric_pulls.inc(outcome="ok" if ok else "error")
        if self.flight is not None:
            self.flight.record(
                "fabric.pulled" if ok else "fabric.pull_failed",
                source=source,
                prompt_tokens=len(prompt),
                restored=int(result.get("restored", 0)),
                reason=result.get("reason", ""),
            )
        return result

    def fabric_drop(self, prompt: list, adapter: Optional[int] = None) -> dict:
        """Router-driven eviction (``POST /debug/fabric/drop``): release
        this replica's HOST-ARENA copies of every cumulative full-page
        key of ``prompt`` (plus the shipped admission logits).  Live and
        retained device pages are deliberately untouched — they are
        refcounted serving state owned by local traffic, and a replica
        still warm in the trie legitimately remains an owner; the drop
        only reclaims the bytes replication put here."""
        ps = self.paged.page_size
        root = self._trie_root(adapter)
        dropped = 0
        with self._lock:
            for i in range(len(prompt) // ps):
                key = ("prefix", root, tuple(prompt[: (i + 1) * ps]))
                if self._kv_arena.pop(key) is not None:
                    dropped += 1
            self._kv_arena.pop(("logits", root, tuple(prompt)))
            if dropped:
                self.fabric_drops += 1
        if dropped and self.metrics:
            self.metrics.fabric_drops.inc()
        if dropped and self.flight is not None:
            self.flight.record(
                "fabric.dropped",
                prompt_tokens=len(prompt),
                entries=dropped,
            )
        return {"ok": True, "dropped": dropped}

    def fabric_state(self) -> dict:
        """JSON-safe fabric snapshot: the body of ``GET /debug/fabric``
        on the engine (the router has its own locator-side view)."""
        digest = self.fabric_digest()
        with self._lock:
            return {
                "enabled": digest is not None,
                "digest": digest,
                "advertised_roots": int(digest["count"]) if digest else 0,
                "digest_builds": self.fabric_digest_builds,
                "arena_version": self._kv_arena.version,
                "trie_version": self._trie_version,
                "pulls": self.fabric_pulls,
                "pull_failures": self.fabric_pull_failures,
                "drops": self.fabric_drops,
            }


# ------------------------------------------------- logits wire section


def encode_logits_section(arr: np.ndarray) -> bytes:
    """``LOGITS_MAGIC | meta | blob``: the optional trailing section of
    a /v1/prefill stream carrying the prefill side's last-position
    logits (same meta/CRC discipline as the entries)."""
    import json as json_mod
    import struct
    import zlib

    blob = np.ascontiguousarray(arr).tobytes()
    meta = json_mod.dumps(
        {
            "dtype": str(arr.dtype),
            "shape": [int(d) for d in arr.shape],
            "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
            "nbytes": len(blob),
        }
    ).encode()
    return LOGITS_MAGIC + struct.pack("<I", len(meta)) + meta + blob


def read_logits_section(f) -> Optional[np.ndarray]:
    """Parse the optional logits section off ``f`` (positioned right
    after the last entry).  Returns None at a clean EOF (the donor had
    no logits to ship); raises :class:`~.engine_snapshot.SnapshotError`
    on a torn or corrupt section — the caller ignores the logits and
    keeps the already-verified entries."""
    import json as json_mod
    import struct
    import zlib

    magic = f.read(len(LOGITS_MAGIC))
    if not magic:
        return None
    if magic != LOGITS_MAGIC:
        raise snap.SnapshotError("bad logits-section magic")
    (meta_len,) = struct.unpack("<I", snap._read_exact(f, 4))
    try:
        meta = json_mod.loads(snap._read_exact(f, meta_len))
    except ValueError as e:
        raise snap.SnapshotError(f"bad logits meta: {e}") from None
    blob = snap._read_exact(f, int(meta["nbytes"]))
    if (zlib.crc32(blob) & 0xFFFFFFFF) != int(meta["crc32"]):
        raise snap.SnapshotError("logits checksum mismatch")
    return np.frombuffer(
        blob, dtype=snap._resolve_dtype(meta["dtype"])
    ).reshape(tuple(meta["shape"]))


# --------------------------------------------------------- decode fetch


def fetch_prefill(
    engine,
    source: str,
    prompt: list,
    adapter: Optional[int] = None,
    timeout_s: float = 30.0,
    trace_context: Optional[str] = None,
    resident_only: bool = False,
) -> dict:
    """Decode-side pull: ``POST /v1/prefill`` on ``source``
    (``"host:port"`` — the router's ``X-Handoff-Source`` locator),
    parse the streamed entries through the snapshot verifier
    (per-entry CRC, full layout compare, entry count), and admit them
    into this engine's host arena so the request's admission restores
    instead of recomputing.

    Parse happens BEFORE admit, so ANY failure — the prefill replica
    dying mid-transfer, a torn stream, a 409/503 refusal, an
    unreachable peer — admits NOTHING and the caller degrades to
    ordinary local prefill (the existing arena contents are untouched:
    unlike the join-time peer fetch, a per-request failure must not
    throw away a serving replica's warm state).  Meters
    ``tpu_engine_handoff_fetches_total{outcome}``; the
    ``engine.handoff.fetch`` failpoint injects dial failure (``error``)
    or a truncated read (``truncate[:fraction]``)."""
    import http.client
    import io
    import json as json_mod

    if not engine._kv_arena.enabled:
        if engine.metrics:
            engine.metrics.handoff_fetches.inc(outcome="disabled")
        return {"ok": False, "reason": "arena_disabled", "restored": 0,
                "source": source}
    t0 = time.perf_counter()
    with engine._lock:
        expected_layout = snap.snapshot_layout(engine)
        expected_fp = snap.params_fingerprint(engine.params)
    host, _, port = source.rpartition(":")
    outcome = "corrupt"
    try:
        hit = failpoints.fire("engine.handoff.fetch", source=source)
        outcome = "unreachable"  # failures below here until parse starts
        conn = http.client.HTTPConnection(host, int(port), timeout=timeout_s)
        try:
            headers = {
                "Content-Type": "application/json",
                snap.LAYOUT_HEADER: snap.layout_fingerprint(expected_layout),
                snap.PARAMS_HEADER: expected_fp,
            }
            if resident_only:
                # Fabric any-peer pull: the owner must already hold the
                # pages — a probe on the peer would move the prefill to
                # the WRONG replica instead of degrading it to local.
                headers[FABRIC_RESIDENT_ONLY_HEADER] = "1"
            if trace_context:
                from ..utils.spans import TRACE_CONTEXT_HEADER

                headers[TRACE_CONTEXT_HEADER] = trace_context
            body = {"prompt": [int(t) for t in prompt]}
            if adapter is not None:
                body["adapter"] = int(adapter)
            conn.request(
                "POST", PREFILL_ROUTE, json_mod.dumps(body).encode(), headers
            )
            resp = conn.getresponse()
            if resp.status != 200:
                outcome = "refused"
                raise snap.SnapshotError(
                    f"prefill source refused: HTTP {resp.status}"
                )
            outcome = "corrupt"  # transport/parse failures from here on
            reader = resp
            if hit is not None and hit.mode == "truncate":
                data = resp.read()
                frac = float(hit.arg) if hit.arg else 0.5
                reader = io.BytesIO(data[: int(len(data) * frac)])
            _, entries = snap._parse_snapshot(
                reader, expected_layout, expected_fp
            )
            # Optional trailing logits: a torn/corrupt section is
            # ignored (the entries above already verified whole — the
            # decode side just pays one tail chunk instead).
            try:
                logits = read_logits_section(reader)
            except (snap.SnapshotError, OSError, ValueError):
                logits = None
        finally:
            conn.close()
        restored = snap._admit_entries(engine, entries)
        if logits is not None:
            with engine._lock:
                engine._kv_arena.put(
                    (
                        "logits",
                        engine._trie_root(adapter),
                        tuple(int(t) for t in prompt),
                    ),
                    {"logits": logits},
                    logits.nbytes,
                )
    except (
        failpoints.FailpointError, snap.SnapshotError, OSError, ValueError,
    ) as e:
        reason = str(e)
        if reason in ("layout_mismatch", "params_mismatch"):
            outcome = reason
        with engine._lock:
            engine.handoff_fetches += 1
            engine.handoff_fetch_failures += 1
        if engine.metrics:
            engine.metrics.handoff_fetches.inc(outcome=outcome)
        if engine.flight is not None:
            engine.flight.record(
                "handoff.fetch_failed",
                source=source, reason=reason, outcome=outcome,
            )
        return {"ok": False, "reason": reason, "outcome": outcome,
                "restored": 0, "source": source}
    with engine._lock:
        engine.handoff_fetches += 1
        engine.handoff_fetched_entries += restored
    if engine.metrics:
        engine.metrics.handoff_fetches.inc(outcome="ok")
        if restored:
            engine.metrics.handoff_entries.inc(restored, direction="fetched")
    result = {
        "ok": True,
        "source": source,
        "restored": restored,
        "logits": logits is not None,
        "ms": round((time.perf_counter() - t0) * 1e3, 3),
    }
    if engine.flight is not None:
        engine.flight.record("handoff.fetched", **result)
    return result
