"""Serving-engine page-pool and device-table management.

Split out of engine.py (round 4): everything that allocates, publishes,
shares, reclaims, or frees KV-cache pages lives here, mixed into
ServingEngine (which owns the state: ``free_pages``, ``_page_refs``, the
prefix trie, the per-slot page chains, and the device cache tree).
Invariants are documented on each method; the capacity model is on the
engine module docstring.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .engine_profiler import in_phase


# Compiled cache writers: pure functions of their arguments (like
# engine_sampling's builders), jitted and cached by
# PagingMixin._cache_writer with the state they write donated: the cache
# tree and the chain (graft, slot), or the chain alone (chain).  The
# first two walk whatever the tree holds — every layer, every ``pool_*``
# leaf (int8 scale pools ride along), every per-slot ``slot_*`` leaf of
# a layer's other subtrees — so no engine variant needs its own.
#
# Two kinds of cached unit: ``pool_*`` leaves under ``attn`` are pages
# keyed by a table (what a sequence holds grows with its length);
# ``slot_*`` leaves (``[slots, ...]``, any subtree but ``attn``: a
# mixer's recurrent state, models/ssm.py, or a linear attention's,
# models/kda.py) are one fixed-size row a slot.  A layer may hold only
# the second kind: a linear-attention layer has no ``attn`` subtree, no
# pages, no table and no lengths, and every walker here passes it by.
# A graft copies the prefilled row into the slot, a slot-row write
# without a graft leaves the slot with no state: zeros.


def slot_leaves(layer: dict):
    """(subtree, leaf) names of a layer's per-slot leaves."""
    return [
        (sub, leaf)
        for sub, tree in layer.items()
        if sub != "attn"
        for leaf in tree
        if leaf.startswith("slot_")
    ]


def cache_bytes_per_token(cache: dict, latent_row: Optional[int] = None) -> tuple[int, int]:
    """Device bytes one cached position takes over all layers, as (row,
    pad): the row the model defines in every ``pool_*`` leaf (K and V with
    their scales, or one latent row of ``latent_row`` values) and the
    lanes a ``pool_latent`` stores beyond it (models/mla.py stores a row
    lane-aligned).  Padding is storage, not work: what a step reads and
    writes of a position is ``row``."""
    row = pad = 0
    for layer in cache.values():
        for name, leaf in layer.get("attn", {}).items():
            if not name.startswith("pool_"):
                continue
            stored = leaf.size // (leaf.shape[0] * leaf.shape[1])
            used = latent_row if name == "pool_latent" else stored
            row += used * leaf.dtype.itemsize
            pad += (stored - used) * leaf.dtype.itemsize
    return row, pad


def slot_state_bytes(cache: dict) -> int:
    """Device bytes of every per-slot leaf of the cache tree (arrays, or
    the shapes ``decode_cache_spec`` gives)."""
    return sum(
        layer[sub][leaf].size * layer[sub][leaf].dtype.itemsize
        for layer in cache.values()
        for sub, leaf in slot_leaves(layer)
    )


def _write_slot_row(cache, chain, slot, length, row, derive_tables: bool, dense=None, row_idx=None):
    """seq_lens[slot] = length in every layer, and ``row`` into the chain
    (derive-tables engines: the per-layer tables are derived in-program
    and overwritten before any read) or into every layer's table.  Row
    ``slot`` of every per-slot leaf becomes row ``row_idx`` of the dense
    prefill cache's same leaf, or zeros where no prefill is given."""
    out = {}
    for name, layer in cache.items():
        new_layer = dict(layer)
        if "attn" in layer:
            att = layer["attn"]
            new_att = {**att, "seq_lens": att["seq_lens"].at[slot].set(length)}
            if not derive_tables:
                new_att["page_table"] = att["page_table"].at[slot].set(row)
            new_layer["attn"] = new_att
        for sub, leaf in slot_leaves(layer):
            state = 0
            if dense is not None:
                state = jax.lax.dynamic_index_in_dim(
                    dense[name][sub][leaf], row_idx, 0, keepdims=False
                ).astype(layer[sub][leaf].dtype)
            new_layer[sub] = {**new_layer[sub], leaf: layer[sub][leaf].at[slot].set(state)}
        out[name] = new_layer
    if derive_tables:
        chain = chain.at[slot].set(row)
    return out, chain


def build_slot_writer(derive_tables: bool):
    """``set_slot(cache, chain, meta, row)`` with ``meta`` = int32
    [slot, length] and ``row`` int32 [max_pages_per_seq].  The slot's
    per-slot leaves are zeroed: teardown, or a slot pointed at pages
    whose sequence state nobody carried over."""

    def set_slot(cache, chain, meta, row):
        return _write_slot_row(cache, chain, meta[0], meta[1], row, derive_tables)

    return set_slot


def build_graft_writer(derive_tables: bool):
    """``graft(cache, chain, dense, meta, row)``: ``meta`` = int32
    [slot, plen, row_idx, n_shared], ``row`` the slot's device row
    (int32 [max_pages_per_seq]; its first ceil(plen / page_size)
    entries are the prompt's pages in either engine kind).

    Per pool: row ``row_idx`` of the matching dense slab
    (``cached_<x>`` for ``pool_<x>``), positions >= plen zeroed, viewed
    as whole pages and scattered page-indexed.  Pages below n_shared
    (a concurrent reader owns them) and pages the prompt does not reach
    are sent to an out-of-range index and DROPPED, so the program's
    shape depends on the dense cache's [batch, bucket] alone.

    Per per-slot leaf (``slot_*``): row ``row_idx`` of the dense cache's
    same leaf, whole, in the same program."""

    def graft(cache, chain, dense, meta, row):
        slot, plen, row_idx, n_shared = meta[0], meta[1], meta[2], meta[3]

        def write_pages(pool, slab):
            num_pages, ps = pool.shape[:2]
            bucket = slab.shape[1]
            n_pg = -(-bucket // ps)
            rows = jax.lax.dynamic_index_in_dim(slab, row_idx, 0, keepdims=False)
            tail = ((0, 0),) * (rows.ndim - 1)
            rows = jnp.pad(rows, ((0, n_pg * ps - bucket),) + tail)
            live = (jnp.arange(n_pg * ps) < plen).reshape((-1,) + (1,) * len(tail))
            rows = jnp.where(live, rows, 0).reshape(n_pg, ps, *rows.shape[1:])
            page = jnp.arange(n_pg)
            private = (page >= n_shared) & (page * ps < plen)
            idx = jnp.where(private, row[:n_pg], num_pages)
            return pool.at[idx].set(rows.astype(pool.dtype), mode="drop")

        grafted = {}
        for name, layer in cache.items():
            if "attn" not in layer:
                grafted[name] = layer  # state alone: _write_slot_row copies it
                continue
            att, src = layer["attn"], dense[name]["attn"]
            pools = {
                pool: write_pages(att[pool], src["cached_" + pool[len("pool_"):]])
                for pool in att
                if pool.startswith("pool_")
            }
            grafted[name] = {**layer, "attn": {**att, **pools}}
        return _write_slot_row(
            grafted, chain, slot, plen, row, derive_tables, dense, row_idx
        )

    return graft


def build_chain_writer():
    """``write_chain(chain, updates)`` with ``updates`` int32 [n, 3], a row a
    grown page: (slot, logical page index, page id).  The row count is
    fixed a program (PagingMixin._chain_updates_len); rows past what a
    pass grew name slot ``chain.shape[0]``, out of range, and are
    DROPPED, as build_graft_writer drops the pages a prompt does not
    reach.  The pools are no operands: one array in, one array out."""

    def write_chain(chain, updates):
        slot, idx, page = updates[:, 0], updates[:, 1], updates[:, 2]
        return (chain.at[slot, idx].set(page, mode="drop"),)

    return write_chain


class PagingMixin:
    """Page allocation/free, prefix-sharing trie, frontier publication,
    windowed reclamation, and the prefill->pages graft."""

    def _cache_writer(self, key: tuple, build, state: tuple):
        """The compiled writer ``key`` over ``state``, the device arrays
        it rewrites: its leading operands, all DONATED, and its whole
        output.  ``build`` makes the traced function on first use of
        ``key``; the jitted program is cached on THIS instance (like
        _prefill_chunk_fn) and, on a mesh, its outputs are pinned to the
        shardings ``state`` already has, so a pool (or the chain) never
        comes back replicated."""
        fn = self._cache_writers.get(key)
        if fn is None:
            self._wd_grace(f"compile:cache_write_{key[0]}")
            pinned = None
            if self.mesh is not None:
                pinned = jax.tree.map(lambda leaf: leaf.sharding, state)
            fn = self._cache_writers[key] = jax.jit(
                build(),
                donate_argnums=tuple(range(len(state))),
                out_shardings=pinned,
            )
        return fn

    def _cache_write(self, key: tuple, build, *args) -> None:
        """Run one compiled writer over the device cache tree:
        ``self.cache, self._chain = writer(self.cache, self._chain, *args)``
        with both DONATED (_cache_writer), so the pools are written in
        place — no pool copy, one dispatch whatever the layer count.
        Host-built ``args`` must already be placed (_rep).  Counts one
        dispatch under the operation, ``key[0]``."""
        op = key[0]
        state = (self.cache, self._chain)
        self.cache, self._chain = self._cache_writer(key, build, state)(
            *state, *args
        )
        self.cache_write_dispatches[op] += 1
        if self.metrics:
            self.metrics.cache_write_dispatches.inc(op=op)
            self.metrics.cache_write_programs.set(self.cache_write_programs())

    def _chain_updates_len(self) -> int:
        """Rows of the chain writer's ``updates`` operand: what one
        _ensure_frontier pass can at most grow, every slot by the pages
        of an overlapped pair of decode blocks and one more."""
        per_slot = -(-2 * self._decode_block // self.paged.page_size) + 1
        return self.max_slots * per_slot

    def _chain_write(self, grown: list[tuple[int, int, int]]) -> None:
        """Publish a pass's page growth, (slot, logical index, page)
        triples, to the device chain: ONE dispatch of ONE compiled
        program (build_chain_writer) over the chain alone — the pools
        are no operands of it, so nothing of the cache tree rides the
        dispatch.  The operand's shape is fixed (_chain_updates_len), so
        the number of pages compiles nothing.  No caller outgrows it (a
        lookahead is at most 2T-1 and speculative engines record
        nothing); a pass that did would write in several dispatches of
        the same program rather than compile another shape or stop the
        loop, which only tests/test_engine_frontier.py reaches."""
        n = self._chain_updates_len()
        for at in range(0, len(grown), n):
            part = grown[at : at + n]
            updates = np.full((n, 3), self.max_slots, np.int32)
            updates[: len(part)] = part
            state = (self._chain,)
            (self._chain,) = self._cache_writer(
                ("chain",), build_chain_writer, state
            )(*state, self._rep(updates))
            self.chain_write_dispatches += 1
            self.chain_pages_written += len(part)
            if self.metrics:
                self.metrics.chain_write_dispatches.inc()
                self.metrics.chain_pages_written.inc(len(part))
                self.metrics.cache_write_programs.set(self.cache_write_programs())

    def cache_write_programs(self) -> int:
        """Compiled cache writers this engine holds: one per dense
        (batch, bucket) shape a graft has seen, the slot-row writer and,
        once optimistic admission grew a page, the chain writer.
        Counted from the jit caches, so a writer that recompiled for a
        prompt length or a page count would show."""
        return sum(fn._cache_size() for fn in list(self._cache_writers.values()))

    def cache_writes_state(self) -> dict:
        """The ``cache_writes`` block of ``GET /debug/profile``: the same
        numbers as tpu_engine_cache_write_dispatches_total,
        tpu_engine_cache_write_programs and, under ``chain``,
        tpu_engine_chain_write_dispatches_total and
        tpu_engine_chain_pages_written_total."""
        return {
            "dispatches": dict(self.cache_write_dispatches),
            "programs": self.cache_write_programs(),
            "chain": {
                "dispatches": self.chain_write_dispatches,
                "pages": self.chain_pages_written,
            },
        }

    def _slot_row(self, pages: list[int], length: int) -> tuple[np.ndarray, int]:
        """The device row for a slot holding ``pages`` at ``length``
        consumed positions, and how many pages it publishes.  Only the
        pages the NEXT decode step can touch are visible: those covering
        positions [0, length] (the first decode write lands at position
        ``length``; a speculative round writes up to length+gamma).  The
        rest stays at scratch page 0 until the frontier reaches it, so
        the kernel's pipeline never streams unwritten generation pages.
        Derive-tables engines record the FULL chain in the [slots,
        max_pages] chain array and the jitted step computes the visible
        prefix in-program; speculative engines publish the visible
        prefix into every layer's table and extend via
        _extend_frontier."""
        n_publish = min(
            (length + self._spec_gamma) // self.paged.page_size + 1, len(pages)
        )
        n = len(pages) if self._derive_tables else n_publish
        row = np.zeros((self.paged.max_pages_per_seq,), np.int32)
        row[:n] = pages[:n]
        return row, n_publish

    def _set_slot_row(self, slot: int, length: int, pages: list[int]) -> None:
        """Point ``slot`` at ``pages`` with ``length`` consumed positions
        on the device: seq_lens[slot] in every layer and the chain row
        (or every layer's table row), in ONE dispatch.  For a slot whose
        K/V rows are already in place (restore-resume, handoff admit)
        and, with no pages and length 0, for teardown."""
        row, n_publish = self._slot_row(pages, length)
        self._cache_write(
            ("slot",),
            lambda: build_slot_writer(self._derive_tables),
            self._rep(np.asarray([slot, length], np.int32)),
            self._rep(row),
        )
        self._slot_visible[slot] = n_publish

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
        """Copy row ``row_idx`` of a prefilled dense cache into the
        slot's PRIVATE prompt pages and point the slot's row/length at
        the chain (_slot_row) — ONE dispatch of one compiled program
        (build_graft_writer) over every pool of every layer, keyed by
        the dense cache's (batch, bucket) shape alone: prompt length,
        shared-page count and chain are traced arguments, so a new
        prompt length compiles nothing.

        Shared prefix pages (the first ``n_shared``) are never rewritten:
        a concurrent request is reading them, and K/V from a prefill
        compiled at a different prompt length are not guaranteed bitwise
        identical — rewriting could perturb an in-flight generation.
        Private pages are written whole; tail slots past plen carry zeros,
        which later appends overwrite before any masked read can see
        them.  No other page of a pool is touched."""
        row, n_publish = self._slot_row(pages, plen)
        att = dense_cache[self._layer_names[0]]["attn"]
        # Any dense slab: every ``cached_*`` leaf is [batch, bucket, ...]
        # (K and V, int8 scales beside them, or one latent row).
        slab = next(att[leaf] for leaf in sorted(att) if leaf.startswith("cached_"))
        self._cache_write(
            ("graft", *slab.shape[:2]),
            lambda: build_graft_writer(self._derive_tables),
            dense_cache,
            self._rep(np.asarray([slot, plen, row_idx, n_shared], np.int32)),
            self._rep(row),
        )
        self._slot_visible[slot] = n_publish

    @in_phase("finish.clear_slot")
    def _clear_slot(self, slot: int):
        """Tear a slot down: zero its device row, length and visible
        page count (one dispatch, _set_slot_row), release its pages,
        reset its host scalars."""
        self._set_slot_row(slot, 0, [])
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
        active list minus anything evicted.

        Derive-tables engines record the pages handed out during the
        pass on the host and publish them to the device chain in ONE
        dispatch (_chain_write) when the pass ends, before it returns —
        so before any decode dispatch that follows.  An eviction inside
        the pass zeroes the victim's chain row on the device AT ONCE
        (_clear_slot) and its freed page ids go to other slots, so what
        was recorded for a slot that is no longer ready at the end is
        DROPPED, never flushed: a slot cannot be re-admitted inside a
        pass, and a write landing after that zero would point an empty
        row at pages that are somebody else's."""
        if not self._optimistic:
            for s in active:
                self._extend_frontier(s, lookahead=lookahead)
            return active
        ps = self.paged.page_size
        grown: list[tuple[int, int, int]] = []  # (slot, logical index, page)
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
                            grown.append((s, idx, page))
                        continue
                if not self._preempt_newest(newer_than=self._slot_seq[s]):
                    break
            if need > self._slot_page_base[s] + len(self._slot_pages[s]):
                self._evict_slot(s)  # starved even after preempting: resume later
                continue
            self._extend_frontier(s, lookahead=lookahead)
        grown = [g for g in grown if self._slot_ready[g[0]]]
        if grown:
            self._chain_write(grown)
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
