"""Serving-engine KV cache tiering: retained pages + host-RAM offload.

The prefix trie (engine_paging.py) only shares KV pages while some live
request still references them — ``_release_page`` frees a page the
instant its refcount hits zero, so a hot system prompt is recomputed
whenever request lifetimes don't overlap, and every preemption throws
away all generated K/V for a full recompute-resume.  This module turns
both recomputes into restores with two tiers layered UNDER the existing
page lifecycle (mixed into ServingEngine like the other engine_* files):

- **Tier 1 — retained device pages.**  When a prefix-registered page's
  refcount drops to zero it moves to an LRU "retained" set instead of
  the free pool; its trie links stay live, so a later same-prefix
  request (or the same request resuming after preemption) matches it
  through the ordinary ``_match_prefix`` walk for free.  The allocator
  reclaims retained pages lazily — LRU order, leaf-first so surviving
  chains stay walkable — and only when ``free_pages`` alone cannot
  satisfy a request, which preserves the pool's liveness guarantee
  (a retained page is always one reclaim away from being free).

- **Tier 2 — host-RAM offload.**  Before a retained page is reclaimed
  its per-layer K/V rows are copied into a bounded numpy arena
  (byte-budgeted via ``--kv-host-cache-mb``; LRU-evicted).  Arena
  entries are keyed by the CUMULATIVE token prefix the page covers —
  content-addressed, so a restore can never alias another request's
  K/V even across page-id reallocation — and a trie walk that runs
  past the device tiers continues into the arena: each hit is restored
  into a fresh device page with one sliced ``.at[pages].set`` per pool
  per layer (no new jit shapes, no recompute) and re-linked into the
  trie.

- **Preemption restore-resume.**  ``_evict_slot`` publishes the
  victim's full pages into the trie (so tier 1 retains them) and
  snapshots the partial tail page plus the tiny decode state (consumed
  length, last emitted token) under the request id.  When the victim
  reaches the queue head again, ``_kv_try_restore_resume`` rebuilds the
  slot EXACTLY as it was — pages matched from the retained tier and/or
  restored from the arena, tail rows written back, seq_lens/table row
  set — and skips prefill entirely: the next ordinary decode step feeds
  the last token at its old position, which is bit-identical to never
  having been evicted.  Any coverage gap (arena evicted the entries)
  falls back to the ordinary recompute-resume path.

Correctness bar, enforced by tests/test_engine_kvcache.py: token
streams are bit-identical with tiering on vs off (restored rows are the
bytes the original graft/appends wrote, and recompute at the same
length bucket writes the same bytes), and a freed-then-reallocated page
id is never reachable through a retained trie link (reclaim runs the
same teardown as a free, and leaf-first ordering plus the existing
parent-death child-unlink rule cover every interleaving).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


class HostKVArena:
    """Bounded host-RAM store for offloaded KV pages and resume snapshots.

    One ``OrderedDict`` doubles as storage and LRU order; ``put`` evicts
    oldest-first until the byte budget holds.  Keys are content-shaped
    tuples: ``("prefix", trie_root, tokens)`` for offloaded full pages
    (shareable across requests) and ``("snap", rid)`` for a preempted
    request's private tail + decode state.  All access happens under the
    engine lock (owner thread plus locked debug readers), so the arena
    itself carries no lock.
    """

    def __init__(self, budget_bytes: int = 0):
        self.budget_bytes = int(budget_bytes)
        self._entries: "OrderedDict[tuple, dict]" = OrderedDict()
        self.bytes = 0
        self.evictions = 0
        # Monotonic mutation counter: bumped on every put/pop/clear and
        # per eviction.  The fabric digest (engine_handoff.py) caches
        # its bloom against this + the trie version, so the cheap
        # summary poll never rebuilds an unchanged filter.
        self.version = 0

    @property
    def enabled(self) -> bool:
        return self.budget_bytes > 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    def get(self, key: tuple, bump: bool = True) -> Optional[dict]:
        entry = self._entries.get(key)
        if entry is not None and bump:
            self._entries.move_to_end(key)
        return entry

    def put(self, key: tuple, entry: dict, nbytes: int) -> int:
        """Insert (or refresh) one entry; returns how many LRU entries
        the byte budget evicted to make room.  An entry larger than the
        whole budget is refused rather than wiping the arena for it."""
        if not self.enabled or nbytes > self.budget_bytes:
            return 0
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes -= old["nbytes"]
        entry = {**entry, "nbytes": int(nbytes)}
        self._entries[key] = entry
        self.bytes += entry["nbytes"]
        self.version += 1
        evicted = 0
        while self.bytes > self.budget_bytes:
            _, victim = self._entries.popitem(last=False)
            self.bytes -= victim["nbytes"]
            self.evictions += 1
            self.version += 1
            evicted += 1
        return evicted

    def prefix_keys(self) -> list[tuple]:
        """Content keys of the offloaded full-page ``("prefix", ...)``
        entries — the fabric digest's arena contribution (snapshot
        donors iterate ``_entries`` directly).  Caller holds the engine
        lock like every other arena access."""
        return [key for key in self._entries if key[0] == "prefix"]

    def pop(self, key: tuple) -> Optional[dict]:
        entry = self._entries.pop(key, None)
        if entry is not None:
            self.bytes -= entry["nbytes"]
            self.version += 1
        return entry

    def clear(self) -> None:
        self._entries.clear()
        self.bytes = 0
        self.version += 1


class KVCacheMixin:
    """Tiered KV cache lifecycle, mixed into ServingEngine.

    Hooks into the page lifecycle at exactly three seams: the
    refcount-zero branch of ``_release_page`` (retain instead of free),
    the two pool-dry points (``_admit`` and ``_ensure_frontier`` reclaim
    lazily before blocking/preempting), and ``_evict_slot``/``_admit``
    for the preemption snapshot/restore pair.  Everything here runs on
    the owner thread under the engine lock except ``kvcache_state``,
    which takes the lock itself for debug readers.
    """

    def _init_kvcache(self, kv_retain: bool, kv_host_cache_mb: float) -> None:
        if kv_host_cache_mb < 0:
            raise ValueError(
                f"kv_host_cache_mb must be >= 0, got {kv_host_cache_mb}"
            )
        self._kv_retain = bool(kv_retain)
        self._kv_arena = HostKVArena(int(kv_host_cache_mb * 1024 * 1024))  # guarded by: _lock
        # Retained tier: page id -> None, insertion order = LRU order
        # (move_to_end on retain refreshes recency).  Only refcount-zero,
        # trie-linked pages ever live here.
        self._kv_retained: "OrderedDict[int, None]" = OrderedDict()  # guarded by: _lock
        # Host-visible counters (exported via metrics when wired, and
        # through kvcache_state / the perf ledger).
        self.kv_retained_hits = 0
        self.kv_host_hits = 0
        self.kv_restores = 0  # host->device page restores
        self.kv_reclaims = 0  # retained pages returned to the free pool
        self.kv_offloads = 0  # pages copied into the host arena
        self.kv_resumes_restored = 0
        self.kv_resumes_recompute = 0
        self.kv_resume_restored_tokens = 0
        self.kv_resume_recomputed_tokens = 0
        # Restore-resumes refused because the slot holds recurrent state
        # the snapshot does not carry (_kv_try_restore_resume).
        self.kv_restore_resume_bypassed = 0

    # ------------------------------------------------------------- tier 1

    def _kv_retain_page(self, page: int) -> bool:  # caller holds: _lock
        """Refcount just hit zero: keep the page (trie links intact) when
        it is reachable — i.e. registered in the trie.  Unregistered
        pages (generation tails, orphaned by a dead parent) hold nothing
        a future request could match, so they fall through to the free
        pool.  Caller holds the lock."""
        if not self._page_keys.get(page):
            return False
        self._kv_retained[page] = None
        self._kv_retained.move_to_end(page)
        return True

    def _kv_revive(self, page: int) -> None:  # caller holds: _lock
        """A retained page was matched and re-referenced (0 -> 1): pin it
        out of the reclaimable set.  Caller holds the lock."""
        if page in self._kv_retained:
            del self._kv_retained[page]
            self.kv_retained_hits += 1
            if self.metrics:
                self.metrics.kvcache_hits.inc(tier="retained")

    def _kv_pick_reclaim(self, protect: frozenset) -> Optional[int]:
        """Oldest retained page that is not the parent of another
        retained page — leaf-first keeps surviving chains walkable for
        as long as possible (reclaiming a parent unlinks every retained
        descendant via the teardown's child-key sweep).  Falls back to
        pure LRU when every candidate parents another (cannot happen in
        a forest, but the fallback keeps reclaim total)."""
        fallback = None
        for page in self._kv_retained:
            if page in protect:
                continue
            if fallback is None:
                fallback = page
            has_retained_child = any(
                self._prefix_pages.get(key) in self._kv_retained
                for key in self._child_keys.get(page, [])
            )
            if not has_retained_child:
                return page
        return fallback

    def _kv_reclaim_page(self, page: int) -> None:  # caller holds: _lock
        """Demote one retained page: offload its rows to the host arena
        (tier 2, content-keyed) when enabled, then run the SAME teardown
        a free runs — every trie link touching the page dies, so a
        reallocated id can never be reached through a stale retained
        link.  Caller holds the lock."""
        self._kv_retained.pop(page, None)
        offloaded = self._kv_offload_page(page)
        self._teardown_page_links(page)
        del self._page_refs[page]
        self.free_pages.append(page)
        self.kv_reclaims += 1
        if self.metrics:
            self.metrics.kvcache_evictions.inc(tier="retained")
        if self.flight is not None:
            self.flight.record(
                "kvcache.evict",
                tier="retained",
                page=page,
                offloaded=offloaded,
                retained_after=len(self._kv_retained),
            )

    def _kv_reclaim(self, need: int, protect: frozenset = frozenset()) -> int:
        """Free up to ``need`` retained pages into the pool (LRU,
        leaf-first); returns how many were freed.  ``protect`` pins
        pages a caller has matched but not yet re-referenced (the
        admission shared list) so reclaim cannot free a page that is
        about to be revived.  Caller holds the lock."""
        freed = 0
        while freed < need and self._kv_retained:
            page = self._kv_pick_reclaim(protect)
            if page is None:
                break
            self._kv_reclaim_page(page)
            freed += 1
        return freed

    # ------------------------------------------------- device <-> host rows

    def _kv_pool_names(self, att: dict) -> list[str]:
        """Every per-page pool in one layer's attention cache (K/V, plus
        int8 scale pools when quant_kv is on)."""
        return [name for name in att if name.startswith("pool_")]

    def _kv_read_page_rows(self, page: int) -> dict:
        """One page's rows across every layer and pool, device -> host.
        Whole-page reads: rows past a partial tail carry garbage exactly
        like a graft's padding — masked until an append overwrites them."""
        rows: dict[str, dict[str, np.ndarray]] = {}
        for name in self._layer_names:
            att = self.cache[name]["attn"]
            rows[name] = {
                pool: np.asarray(att[pool][page])
                for pool in self._kv_pool_names(att)
            }
        return rows

    @staticmethod
    def _kv_rows_nbytes(rows: dict) -> int:
        return sum(
            arr.nbytes for pools in rows.values() for arr in pools.values()
        )

    def _kv_write_page_rows(self, pages: list[int], rows_list: list[dict]) -> None:
        """Restore host rows into device pages: ONE page-indexed scatter
        per pool per layer (the _graft discipline — per-page eager
        ``.at`` updates would round-trip the whole pool once per page).
        Under tensor parallelism the update rows are device_put with the
        pool's own kv-heads spec BEFORE the scatter, so a sharded pool
        round-trips through the host arena without resharding churn (the
        scatter's operands agree on layout and the result keeps the
        pool's placement)."""
        idx = self._rep(jnp.asarray(pages, jnp.int32))
        if self.mesh is not None:
            from ..parallel.serving import cache_leaf_spec
        for name in self._layer_names:
            att = self.cache[name]["attn"]
            new_att = dict(att)
            for pool in self._kv_pool_names(att):
                stacked = jnp.asarray(
                    np.stack([rows[name][pool] for rows in rows_list])
                )
                if self.mesh is not None:
                    # The contract's spec for this pool, applied to the
                    # update rows (same rank: [pages, ...] slices).
                    stacked = jax.device_put(
                        stacked,
                        jax.sharding.NamedSharding(
                            self.mesh,
                            cache_leaf_spec(
                                pool, stacked, self.tp_size, self._tp_axis
                            ),
                        ),
                    )
                new_att[pool] = att[pool].at[idx].set(stacked)
            self.cache[name]["attn"] = new_att

    # ------------------------------------------------------------- tier 2

    def _kv_page_prefix(self, page: int) -> Optional[tuple[int, tuple]]:
        """The cumulative (trie_root, tokens) prefix a registered page
        covers, recovered by walking its ancestry keys — no extra state
        to keep coherent.  None when any ancestor lost its registration
        (the page is trie-unreachable and not worth offloading)."""
        chunks: list[tuple] = []
        node = page
        for _ in range(self.paged.num_pages):
            keys = self._page_keys.get(node)
            if not keys:
                return None
            parent, chunk = keys[0]
            chunks.append(chunk)
            node = parent
            if node < 0:  # pseudo-root: -1 base model, -(2+i) adapter i
                tokens = tuple(t for c in reversed(chunks) for t in c)
                return node, tokens
        return None

    def _kv_offload_page(self, page: int) -> bool:  # caller holds: _lock
        """Copy one retained page's rows into the host arena keyed by its
        cumulative prefix; True when stored.  Caller holds the lock."""
        if not self._kv_arena.enabled:
            return False
        prefix = self._kv_page_prefix(page)
        if prefix is None:
            return False
        root, tokens = prefix
        rows = self._kv_read_page_rows(page)
        evicted = self._kv_arena.put(
            ("prefix", root, tokens), {"rows": rows}, self._kv_rows_nbytes(rows)
        )
        self.kv_offloads += 1
        if self.metrics:
            if evicted:
                self.metrics.kvcache_evictions.inc(evicted, tier="host")
        if evicted and self.flight is not None:
            self.flight.record(
                "kvcache.evict",
                tier="host",
                entries=evicted,
                host_bytes=self._kv_arena.bytes,
            )
        return True

    def _kv_match_host(
        self, eff: list[int], adapter: Optional[int], start: int, stop: int
    ) -> list[dict]:
        """Continue a trie walk into the host arena: consecutive full-page
        entries for eff's pages [start, stop), stopping at the first
        miss (a chain hole cannot be bridged — later pages' K/V depend
        on the missing positions only through content equality, which
        the cumulative key already guarantees, but a hole means the
        device page for it would be unwritten).  Returns the entries in
        page order."""
        if not self._kv_arena.enabled:
            return []
        ps = self.paged.page_size
        root = self._trie_root(adapter)
        out: list[dict] = []
        for i in range(start, stop):
            entry = self._kv_arena.get(("prefix", root, tuple(eff[: (i + 1) * ps])))
            if entry is None:
                break
            out.append(entry)
        return out

    def _kv_restore_pages(self, pages: list[int], rows_list: list[dict]) -> None:
        """Write host-held page rows into freshly allocated device pages
        and meter the restore (counter, latency histogram, flight)."""
        # The page-indexed scatter compiles per page-count shape on first
        # use: grace the hung-step deadline for this step.
        self._wd_grace("kv_restore")
        t0 = time.perf_counter()
        self._kv_write_page_rows(pages, rows_list)
        dt = time.perf_counter() - t0
        self.kv_restores += len(pages)
        self.kv_host_hits += len(pages)
        if self.metrics:
            self.metrics.kvcache_hits.inc(len(pages), tier="host")
            self.metrics.kvcache_restores.inc(len(pages))
            self.metrics.kvcache_restore_seconds.observe(dt)
        if self.flight is not None:
            self.flight.record(
                "kvcache.restore",
                pages=len(pages),
                ms=round(dt * 1e3, 3),
                host_bytes=self._kv_arena.bytes,
            )

    # -------------------------------------------- preemption snapshot/resume

    def _kv_snapshot_slot(self, slot: int, req: Any) -> bool:
        """Preemption epilogue: publish the victim's full pages into the
        trie (so _clear_slot's release RETAINS them — the device stays
        the first tier for its own resume) and snapshot the partial tail
        page plus the decode state under the request id.  True when a
        snapshot was stored (restore-resume becomes possible)."""
        if not self._kv_retain:
            return False
        if self._slot_page_base[slot]:
            return False  # windowed reclaim dropped leading pages: no full chain
        with self._lock:
            L = self._slot_len[slot]
            ps = self.paged.page_size
            n_full = L // ps
            eff = req.prompt + req.tokens
            if self.prefix_sharing and n_full:
                # Publish the full pages (prompt AND generated content)
                # into the trie even when the host arena is off: the
                # release below then retains them, and the resume's
                # ordinary prefix match rides them — a recompute-resume
                # still skips their graft writes.
                self._register_prefix(eff, self._slot_pages[slot], n_full, req.adapter)
            if not self._kv_arena.enabled:
                return False  # no tail/state snapshot -> recompute-resume
            tail = None
            nbytes = 256  # state scalars; tail rows dominate when present
            if L % ps and n_full < len(self._slot_pages[slot]):
                tail = self._kv_read_page_rows(self._slot_pages[slot][n_full])
                nbytes += self._kv_rows_nbytes(tail)
            evicted = self._kv_arena.put(
                ("snap", req.rid),
                {"len": L, "last": self._slot_last[slot], "tail": tail},
                nbytes,
            )
            if evicted and self.metrics:
                self.metrics.kvcache_evictions.inc(evicted, tier="host")
            return ("snap", req.rid) in self._kv_arena

    def _kv_drop_snapshot(self, rid: int) -> None:  # caller holds: _lock
        self._kv_arena.pop(("snap", rid))

    def _kv_try_restore_resume(self, slot: int, req: Any) -> bool:
        """Admission fast path for a preempted request at the queue head:
        rebuild the slot from the tiers and SKIP prefill entirely.

        Requires full coverage — every full page matched live/retained
        (device) or present in the arena, plus the tail snapshot — and
        enough pool pages after a lazy reclaim; anything short returns
        False and the ordinary recompute-resume path runs (restored
        pages still shrink its graft through the shared-prefix count).
        The rebuilt slot is EXACTLY the pre-eviction decode state (same
        consumed length, same pending last token), so the next decode
        step continues bit-identically to never having been evicted.
        Caller holds the lock."""
        snap = self._kv_arena.get(("snap", req.rid), bump=False)
        if snap is None:
            return False
        if self.slot_state_bytes:
            # The snapshot holds K/V rows and scalars, not the slot's
            # recurrent state (models/ssm.py): skipping prefill would
            # resume from zeros.  Recompute-resume rebuilds the state
            # (its retained pages still shrink the graft); counted.
            self._kv_drop_snapshot(req.rid)
            self.kv_restore_resume_bypassed += 1
            if self.metrics:
                self.metrics.restore_resume_bypassed.inc()
            return False
        L = snap["len"]
        ps = self.paged.page_size
        eff = req.prompt + req.tokens
        if L + 1 != len(eff):  # stale snapshot (should not happen): recompute
            self._kv_drop_snapshot(req.rid)
            return False
        n_full = L // ps
        n_pages = n_full + 1  # content pages + the page position L writes into
        if n_pages > self.paged.max_pages_per_seq:
            return False
        bucket = min(1 << (len(eff) - 1).bit_length(), self.paged.max_len)
        shared = (
            self._match_prefix(eff, bucket, {}, req.adapter)[:n_full]
            if self.prefix_sharing
            else []
        )
        host = self._kv_match_host(eff, req.adapter, len(shared), n_full)
        if len(shared) + len(host) < n_full:
            # Arena budget evicted part of the chain: recompute-resume.
            self._kv_drop_snapshot(req.rid)
            return False
        tail = snap["tail"]
        if L % ps and tail is None:
            self._kv_drop_snapshot(req.rid)
            return False
        n_private = n_pages - len(shared)
        if n_private > len(self.free_pages):
            self._kv_reclaim(
                n_private - len(self.free_pages), protect=frozenset(shared)
            )
        if n_private > len(self.free_pages):
            return False  # pool-blocked: keep the snapshot, retry next step
        self.queue.popleft()
        req.admitted_at = time.monotonic()
        private = [self.free_pages.popleft() for _ in range(n_private)]
        pages = shared + private
        for page in shared:
            self._page_refs[page] += 1
            if self._page_refs[page] == 1:
                self._kv_revive(page)
        for page in private:
            self._page_refs[page] = 1
        restore_pages, restore_rows = [], []
        if host:
            restore_pages += private[: len(host)]
            restore_rows += [e["rows"] for e in host]
        if tail is not None:
            restore_pages.append(pages[n_full])
            restore_rows.append(tail)
        if restore_pages:
            self._kv_restore_pages(restore_pages, restore_rows)
        if self.prefix_sharing and n_full:
            self._register_prefix(eff, pages, n_full, req.adapter)
        self._kv_drop_snapshot(req.rid)

        # Slot state: the _graft/_activate table discipline without the
        # pool writes (the rows are already in place) or the admission
        # token (req.tokens already carries it — it is the pending last
        # token the next decode step feeds at position L).
        self._set_slot_row(slot, L, pages)
        self.slots[slot] = req
        self._slot_pages[slot] = pages
        self._slot_page_base[slot] = 0
        self._slot_len[slot] = L
        self._slot_last[slot] = snap["last"]
        self._slot_seq[slot] = self._seq_counter
        self._seq_counter += 1
        self._set_slot_sampler(slot, req)
        self._slot_ready[slot] = True
        self._slot_emit_t[slot] = time.monotonic()
        self._mark_state_dirty()

        self.kv_resumes_restored += 1
        self.kv_resume_restored_tokens += L
        if self.metrics:
            self.metrics.resumes.inc(mode="restored")
            self.metrics.resume_restored_tokens.inc(L)
        if self.flight is not None:
            self.flight.record(
                "engine.resume",
                rid=req.rid,
                mode="restored",
                restored_tokens=L,
                recomputed_tokens=0,
                pages_shared=len(shared),
                pages_restored=len(restore_pages),
            )
        self._update_gauges()
        return True

    # ------------------------------------------------------------ interface

    def kvcache_clear(self) -> None:
        """Drop both tiers: reclaim every retained page into the free
        pool (no offload — the point is a clean slate) and empty the
        arena.  Benchmarks and tests use this to compare recompute vs
        restore over identical traffic; counters survive."""
        with self._lock:
            for page in list(self._kv_retained):
                self._kv_retained.pop(page, None)
                self._teardown_page_links(page)
                del self._page_refs[page]
                self.free_pages.append(page)
            self._kv_arena.clear()
            self._update_gauges()

    def kvcache_state(self) -> dict:
        """JSON-safe tier snapshot: the body of ``GET /debug/kvcache``
        and the ``kvcache`` block of ``debug_state()``."""
        with self._lock:
            return {
                "retain": self._kv_retain,
                "retained_pages": len(self._kv_retained),
                "host": {
                    "enabled": self._kv_arena.enabled,
                    "budget_bytes": self._kv_arena.budget_bytes,
                    "bytes": self._kv_arena.bytes,
                    "entries": len(self._kv_arena),
                    "evictions": self._kv_arena.evictions,
                },
                "hits": {
                    "retained": self.kv_retained_hits,
                    "host": self.kv_host_hits,
                },
                "restores": self.kv_restores,
                "reclaims": self.kv_reclaims,
                "offloads": self.kv_offloads,
                "resumes": {
                    "restored": self.kv_resumes_restored,
                    "recompute": self.kv_resumes_recompute,
                    "restored_tokens": self.kv_resume_restored_tokens,
                    "recomputed_tokens": self.kv_resume_recomputed_tokens,
                    "restore_bypassed": self.kv_restore_resume_bypassed,
                },
            }
