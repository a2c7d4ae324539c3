"""Serving-engine page-pool and device-table management.

Split out of engine.py (round 4): everything that allocates, publishes,
shares, reclaims, or frees KV-cache pages lives here, mixed into
ServingEngine (which owns the state: ``free_pages``, ``_page_refs``, the
prefix trie, the per-slot page chains, and the device cache tree).
Invariants are documented on each method; the capacity model is on the
engine module docstring.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from .engine_profiler import in_phase


class PagingMixin:
    """Page allocation/free, prefix-sharing trie, frontier publication,
    windowed reclamation, and the prefill->pages graft."""

    @in_phase("prefill.graft")
    def _graft(
        self,
        slot: int,
        dense_cache: Any,
        pages: list[int],
        plen: int,
        n_shared: int,
        row_idx: int = 0,
    ):
        """Scatter a prefilled dense cache's rows into the PRIVATE prompt
        pages and point the slot's table/length at the full chain — ONE
        page-indexed scatter per pool per layer (not per page: eager `.at`
        updates are copy-on-write, so per-page updates would round-trip
        the whole pool once per page).

        Shared prefix pages (the first ``n_shared``) are never rewritten:
        a concurrent request is reading them, and K/V from a prefill
        compiled at a different prompt length are not guaranteed bitwise
        identical — rewriting could perturb an in-flight generation.
        Private pages are written whole; tail slots past plen carry zeros,
        which later appends overwrite before any masked read can see
        them."""
        ps = self.paged.page_size
        n_cover = math.ceil(plen / ps)
        # Publish only the pages the NEXT decode step can touch: those
        # covering positions [0, plen] (the first decode write lands at
        # position plen; a speculative round writes up to plen+gamma).
        # The rest of the chain stays at scratch page 0 until the
        # frontier reaches it so the kernel's pipeline never streams
        # unwritten generation pages.  Derive-tables engines record the
        # FULL chain in the [slots, max_pages] chain array (one device
        # write) and the jitted step computes the visible prefix
        # in-program; speculative engines publish into every layer's
        # cache table here and extend via _extend_frontier.
        n_publish = min((plen + self._spec_gamma) // ps + 1, len(pages))
        self._slot_visible[slot] = n_publish
        if self._derive_tables:
            full = np.zeros((self.paged.max_pages_per_seq,), np.int32)
            full[: len(pages)] = pages
            self._chain = self._chain.at[slot].set(jnp.asarray(full))
        else:
            row = np.zeros((self.paged.max_pages_per_seq,), np.int32)
            row[:n_publish] = pages[:n_publish]
        lo_tok = n_shared * ps  # first private-covered token position
        n_priv_cover = n_cover - n_shared
        cover = jnp.asarray(pages[n_shared:n_cover], jnp.int32)
        pad = n_cover * ps - plen
        for name in self._layer_names:
            att = self.cache[name]["attn"]
            src = dense_cache[name]["attn"]

            def paged_rows(slab):
                rows = slab[row_idx, lo_tok:plen]
                if pad:
                    rows = jnp.pad(
                        rows, ((0, pad),) + ((0, 0),) * (rows.ndim - 1)
                    )
                return rows.reshape(n_priv_cover, ps, *rows.shape[1:])

            new_att = {
                **att,
                "seq_lens": att["seq_lens"].at[slot].set(plen),
            }
            if not self._derive_tables:
                new_att["page_table"] = (
                    att["page_table"].at[slot].set(jnp.asarray(row))
                )
            if n_priv_cover > 0:
                new_att["pool_key"] = (
                    att["pool_key"].at[cover].set(paged_rows(src["cached_key"]))
                )
                new_att["pool_value"] = (
                    att["pool_value"].at[cover].set(paged_rows(src["cached_value"]))
                )
                if "pool_key_scale" in att:
                    # int8 KV: the scale rows CACHE alongside the page
                    # write — the dense prefill quantized once
                    # (quantize_kv_pair) and its scale slabs scatter
                    # here with the codes; nothing later (kernel,
                    # gather, offload, restore) re-derives a scale.
                    # Pool-byte accounting (_kv_rows_nbytes) counts the
                    # two f32 scale pools with the codes — pinned in
                    # tests/test_engine.py.
                    new_att["pool_key_scale"] = (
                        att["pool_key_scale"]
                        .at[cover]
                        .set(paged_rows(src["cached_key_scale"]))
                    )
                    new_att["pool_value_scale"] = (
                        att["pool_value_scale"]
                        .at[cover]
                        .set(paged_rows(src["cached_value_scale"]))
                    )
            self.cache[name]["attn"] = new_att

    @in_phase("finish.clear_slot")
    def _clear_slot(self, slot: int):
        if self._derive_tables:
            # One chain-row zero; per-layer cache tables are derived
            # in-program and overwritten before any read.
            self._chain = self._chain.at[slot].set(0)
        for name in self._layer_names:
            att = self.cache[name]["attn"]
            new_att = {
                **att,
                "seq_lens": att["seq_lens"].at[slot].set(0),
            }
            if not self._derive_tables:
                new_att["page_table"] = att["page_table"].at[slot].set(0)
            self.cache[name]["attn"] = new_att
        for page in self._slot_pages[slot]:
            self._release_page(page)
        self._slot_pages[slot] = []
        self.slots[slot] = None
        self._slot_last[slot] = 0
        self._slot_len[slot] = 0
        self._slot_temp[slot] = 0.0
        self._slot_topk[slot] = self.cfg.vocab_size
        self._slot_topp[slot] = 1.0
        self._slot_bias_ids[slot] = [0] * self.MAX_BIAS
        self._slot_bias_vals[slot] = [0.0] * self.MAX_BIAS
        self._slot_aid[slot] = -1
        self._slot_page_base[slot] = 0
        self._slot_visible[slot] = 0
        self._slot_ready[slot] = False
        self._slot_emit_t[slot] = 0.0
        # Slot scalars changed: the device-resident step state must be
        # rebuilt from host truth before the next dispatch (engine.py).
        self._mark_state_dirty()

    def _release_page(self, page: int) -> None:
        """Drop one reference; at zero, either RETAIN the page (trie
        links intact — the kv-cache tier 1, engine_kvcache.py: a later
        same-prefix request matches it for free, and the allocator
        reclaims it lazily when the pool runs dry) or tear down every
        trie link touching the page and return it to the pool.  The ONE
        page-free path: _clear_slot and windowed reclamation both come
        through here.  Runs under the engine lock: _update_gauges
        iterates _page_refs from the scraping/submitting threads, and a
        resize here mid-iteration would crash them."""
        with self._lock:
            self._page_refs[page] -= 1
            if self._page_refs[page] > 0:
                return
            if self._kv_retain and self._kv_retain_page(page):
                return  # refcount parks at 0; revived on the next match
            del self._page_refs[page]
            self._teardown_page_links(page)
            self.free_pages.append(page)

    def _teardown_page_links(self, page: int) -> None:  # caller holds: _lock
        """Remove every trie link touching a dying page: keys registered
        FOR it and keys in which it is the PARENT — a freed id can be
        reallocated and re-registered with different content, so a
        surviving child link would let a later prompt walk into another
        request's K/V.  Shared by the free path above and the retained-
        tier reclaim (engine_kvcache.py), which must uphold the same
        invariant.  Caller holds the engine lock."""
        for key in self._page_keys.pop(page, []):
            self._prefix_pages.pop(key, None)
            self._trie_version += 1
        for key in self._child_keys.pop(page, []):
            child = self._prefix_pages.pop(key, None)
            if child is not None:
                self._trie_version += 1
                keys = self._page_keys.get(child)
                if keys and key in keys:
                    keys.remove(key)

    @staticmethod
    def _trie_root(adapter: Optional[int]) -> int:
        """Root pseudo-parent for the prefix trie: K/V are a function of
        (params, adapter, tokens), so each adapter gets its own root (-1 =
        base model, -(2+i) = adapter i) and chains never cross adapters.
        Pseudo-roots are never real pages, so they are never freed and
        take no _child_keys bookkeeping (their links die with the child
        page, exactly like the old -1 root's)."""
        return -1 if adapter is None else -(2 + adapter)

    def _match_prefix(
        self,
        prompt: list[int],
        bucket: int,
        burst_pages: dict[int, int],
        adapter: Optional[int] = None,
    ) -> list[int]:
        """Longest chain of live registered pages whose token chunks equal
        this prompt's leading FULL pages (trie walk: O(prompt)).

        A page may only be shared once its content is guaranteed written
        before this request's first decode step: pages of ACTIVATED
        requests always qualify; pages of a still-pending prefill job do
        NOT (the owner's graft is deferred — sharing them would decode
        against zeros), EXCEPT pages admitted in this same burst with the
        same length bucket — those land in the same job, whose _activate
        grafts every item before any of them decodes.
        """
        ps = self.paged.page_size
        pages: list[int] = []
        parent = self._trie_root(adapter)
        for i in range(len(prompt) // ps):
            chunk = tuple(prompt[i * ps : (i + 1) * ps])
            page = self._prefix_pages.get((parent, chunk))
            if page is None:
                break
            if page in burst_pages:
                if burst_pages[page] != bucket:
                    break  # different bucket -> different job -> unsafe
            elif page in self._pending_pages:
                break  # owner's job from an earlier step not grafted yet
            pages.append(page)
            parent = page
        return pages

    def _register_prefix(  # caller holds: _lock
        self, eff: list[int], pages: list[int], n: int, adapter: Optional[int]
    ) -> None:
        """Register ``eff``'s first ``n`` full pages as trie links so
        later same-prefix requests can ride them (idempotent: an
        existing key wins and the walk follows the CANONICAL page, which
        in the admission path is always ``pages[i]`` itself).  Callers:
        the admission burst, the preemption snapshot (publishing a
        victim's generated pages), and restore-resume (re-linking
        restored pages).  Caller holds the engine lock."""
        ps = self.paged.page_size
        parent = self._trie_root(adapter)
        for i in range(n):
            key = (parent, tuple(eff[i * ps : (i + 1) * ps]))
            if key not in self._prefix_pages:
                self._prefix_pages[key] = pages[i]
                self._page_keys.setdefault(pages[i], []).append(key)
                self._trie_version += 1
                if parent >= 0:
                    self._child_keys.setdefault(parent, []).append(key)
            parent = self._prefix_pages[key]

    @in_phase("dispatch.frontier")
    def _ensure_frontier(self, active: list[int], lookahead: int) -> list[int]:
        """Make every coming write in [len, len+lookahead] addressable for
        each active slot, then publish the covering pages.

        ``lookahead`` callers: plain synchronous decode passes 0 (only
        the next position's write), the overlapped pipeline passes 1 (the
        in-flight step's write at len+1 must be addressable BEFORE the
        host has consumed position len), decode blocks pass T-1 — or
        2T-1 with an overlapped block in flight — and speculative rounds
        run gamma lookahead through _extend_frontier directly.

        Reserve admission: pages were all allocated at admission, so this
        is pure publication.  Optimistic admission: generation pages are
        allocated HERE, on demand — processed oldest-admission-first, a
        pool shortage preempts the newest ready slot (recompute-resume:
        the victim requeues at the head and re-prefills prompt+generated),
        and if the shortage persists the starved slot itself is evicted.
        Oldest-first + newest-evicted means the oldest request can never
        be robbed, which is the liveness argument (it eventually owns
        every page its submit-time bound guarantees fit).  Returns the
        active list minus anything evicted."""
        if not self._optimistic:
            for s in active:
                self._extend_frontier(s, lookahead=lookahead)
            return active
        ps = self.paged.page_size
        for s in sorted(active, key=lambda x: self._slot_seq[x]):
            req = self.slots[s]
            if req is None or not self._slot_ready[s]:
                continue  # evicted as a victim earlier in this pass
            need = (self._slot_len[s] + lookahead) // ps + 1
            while need > self._slot_page_base[s] + len(self._slot_pages[s]):
                with self._lock:
                    if not self.free_pages and self._kv_retained:
                        # Retained pages are reclaimable-on-demand: spill
                        # one to the host tier before robbing a newer slot.
                        self._kv_reclaim(1)
                    page = (
                        self.free_pages.popleft() if self.free_pages else None
                    )
                    if page is not None:
                        self._page_refs[page] = 1
                        self._slot_pages[s].append(page)
                        if self._derive_tables:
                            # Record the grown chain; the step publishes
                            # it in-program once the frontier arrives.
                            idx = (
                                self._slot_page_base[s]
                                + len(self._slot_pages[s])
                                - 1
                            )
                            self._chain = self._chain.at[s, idx].set(page)
                        continue
                if not self._preempt_newest(newer_than=self._slot_seq[s]):
                    break
            if need > self._slot_page_base[s] + len(self._slot_pages[s]):
                self._evict_slot(s)  # starved even after preempting: resume later
                continue
            self._extend_frontier(s, lookahead=lookahead)
        return [
            s
            for s in active
            if self.slots[s] is not None and self._slot_ready[s]
        ]

    def _preempt_newest(self, newer_than: int) -> bool:
        """Evict the most recently admitted ready slot STRICTLY newer
        than ``newer_than`` to free its pages; False when none is.  A
        growing slot may only rob younger slots — never an older one —
        so the oldest request's page claim is monotone (liveness)."""
        cands = [
            s
            for s in range(self.max_slots)
            if self.slots[s] is not None
            and self._slot_ready[s]
            and self._slot_seq[s] > newer_than
        ]
        if not cands:
            return False
        self._evict_slot(max(cands, key=lambda s: self._slot_seq[s]))
        return True

    def _evict_slot(self, slot: int) -> None:
        """Preempt: tear the slot down exactly like a finish (pages,
        table row, prefix refcounts all through _clear_slot) but requeue
        the request at the queue HEAD for recompute-resume — unless the
        client already cancelled it, in which case eviction doubles as
        the teardown."""
        req = self.slots[slot]
        # Snapshot BEFORE teardown: the tail page's rows and the decode
        # state scalars (engine_kvcache.py) — _clear_slot's release then
        # RETAINS the full pages (registered below) rather than freeing
        # them, so the victim's own resume matches them device-side.  A
        # racing cancel is reconciled under the lock below.
        snapshotted = (
            self._kv_snapshot_slot(slot, req) if not req.cancelled else False
        )
        self._clear_slot(slot)
        with self._lock:
            # Atomic with cancel(): a disconnect racing this eviction
            # either finds the request still in a slot (cancel marks it;
            # we see cancelled here) or finds it back in the queue
            # (cancel removes it there) — never a cancelled request
            # silently re-admitted.
            if req.cancelled:
                if snapshotted:
                    self._kv_drop_snapshot(req.rid)
                req.done = True
                self._update_gauges()
                return
            # Only a real recompute-resume counts as a preemption: a
            # cancelled victim's eviction is ordinary teardown, and
            # operators size the pool from this counter.
            self.preemptions += 1
            if self.metrics:
                self.metrics.preemptions.inc()
            self.queue.appendleft(req)
            self._update_gauges()
        if self.flight is not None:
            self.flight.record(
                "engine.preempt",
                rid=req.rid,
                generated=len(req.tokens),
                free_pages_after=len(self.free_pages),
                snapshot=snapshotted,
            )

    def _extend_frontier(self, slot: int, lookahead: Optional[int] = None) -> None:
        """Publish every page the next step can write — up to the one
        covering position len+lookahead — into the device table the
        moment the frontier approaches it: tiny .at[slot, idx].set
        updates per layer, amortized O(1/page_size) dispatches per token.
        ``lookahead`` defaults to the speculative gamma (0 for plain
        decode: only the next position's page); decode blocks and the
        overlapped pipeline pass their furthest write via
        _ensure_frontier (see its docstring for the caller table)."""
        if lookahead is None:
            lookahead = self._spec_gamma
        need = (
            self._slot_len[slot] + lookahead
        ) // self.paged.page_size + 1
        need = min(
            need, self._slot_page_base[slot] + len(self._slot_pages[slot])
        )
        if self._derive_tables:
            # Publication happens in-program (the step derives the
            # visible prefix from the chain array); only the host-side
            # watermark advances here, for invariants and tests.
            self._slot_visible[slot] = max(self._slot_visible[slot], need)
            return
        while self._slot_visible[slot] < need:
            idx = self._slot_visible[slot]  # logical page index to publish
            page = self._slot_pages[slot][idx - self._slot_page_base[slot]]
            for name in self._layer_names:
                att = self.cache[name]["attn"]
                self.cache[name]["attn"] = {
                    **att,
                    "page_table": att["page_table"].at[slot, idx].set(page),
                }
            self._slot_visible[slot] = idx + 1

    def _reclaim_windowed(self, slot: int) -> None:
        """Free pages that scrolled fully out of a sliding attention
        window.  A query at position p sees keys in (p - window, p]; once
        every position in a page is below ``len - window`` no future query
        can see it — visibility only moves forward — so the page returns
        to the pool mid-flight (bounded cache memory for long windowed
        decodes).  Its table entry points at the scratch page: gathers of
        masked positions read garbage that the window mask discards, and
        the append frontier is always ahead of the reclaimed region."""
        window = self.cfg.attention_window
        ps = self.paged.page_size
        horizon = self._slot_len[slot] - window
        # horizon // ps = TOTAL pages ever dead for this slot; subtract the
        # already-reclaimed count (the page list is trimmed in place, so
        # reusing the total as an increment would double-free live pages —
        # caught by the windowed-oracle test).
        n_dead = max(
            0,
            min(
                horizon // ps - self._slot_page_base[slot],
                len(self._slot_pages[slot]),
            ),
        )
        if n_dead <= 0:
            return
        dead, self._slot_pages[slot] = (
            self._slot_pages[slot][:n_dead],
            self._slot_pages[slot][n_dead:],
        )
        # The logical page indices shift only in OUR bookkeeping; the
        # device table keeps absolute logical positions, so dead entries
        # are re-pointed at scratch (a sliced device update — no host
        # round-trip) rather than compacted.  A freed id may be
        # reallocated to another request immediately, so the entry MUST
        # be zeroed before the next dispatch — derive-tables engines
        # zero the chain (one array), spec engines every layer's table.
        lo = self._slot_page_base[slot]
        if self._derive_tables:
            self._chain = self._chain.at[slot, lo : lo + n_dead].set(0)
        else:
            for name in self._layer_names:
                att = self.cache[name]["attn"]
                self.cache[name]["attn"] = {
                    **att,
                    "page_table": att["page_table"].at[slot, lo : lo + n_dead].set(0),
                }
        self._slot_page_base[slot] += n_dead
        for page in dead:
            self._release_page(page)
