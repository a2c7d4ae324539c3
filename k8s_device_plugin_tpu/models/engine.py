"""Continuous-batching serving engine over the paged KV cache.

The reference stops at mounting device nodes into a pod (reference
main.go:139-159); this is the workload-side request server that runs ON
those chips.  Design split, TPU-shaped:

- **Device side** (jitted once): a fixed-[slots] single-token decode step
  over the paged cache (models/transformer.py ``PagedConfig``) — every
  slot advances every step, idle slots compute masked garbage into the
  reserved scratch page.  Static shapes, no recompiles as requests come
  and go.
- **Host side** (plain Python between steps): admission, page
  allocation/free, per-slot bookkeeping.  State edits are row-wise
  ``.at[slot].set`` updates on the cache tree — O(layers) small
  dispatches per request event, never per token.

Prefill bridges through the dense path: an admitted prompt runs the
ordinary dense-cache prefill (one MXU-shaped pass, compiled per prompt
length), and its K/V rows are grafted into the allocated pages.  Decode
then proceeds fully paged.  Page 0 is reserved as the idle-slot scratch
target: idle rows keep appending there (their page-table rows are zero
and gather indices clamp), so they can never collide with a live page.

Capacity model: a request needs ``ceil((prompt + max_new) / page_size)``
pages, allocated at admission (no mid-flight allocation → no deadlock);
requests queue when the pool is dry and admit as finished requests free
their pages — continuous batching.

Module layout (round-4 split; this module remains the import surface):

- engine_types.py      — ``Request``, ``EngineMetrics``
- engine_sampling.py   — top-k/top-p filter, jitted step/block builders
- engine_admission.py  — submit/cancel, batched chunked prefill, admission
- engine_paging.py     — page pool, prefix trie, frontier, reclamation
- engine_kvcache.py    — KV cache tiering: retained dead-but-valid pages
  (LRU, reclaimed lazily under pool pressure) + bounded host-RAM offload
  with restore-instead-of-recompute for repeated prefixes and
  preemption resumes
- engine_spec.py       — speculative round builders + host consumption
- here                 — ``ServingEngine`` wiring, step loop (split
  dispatch/consume halves with one decode round in flight — the
  overlapped pipeline; ``overlap_steps=0`` restores the strictly
  synchronous loop), CLI ``main``
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .engine_admission import AdmissionMixin
from .engine_handoff import HandoffMixin
from .engine_kvcache import KVCacheMixin
from .engine_paging import PagingMixin, cache_bytes_per_token, slot_state_bytes
from .engine_sampling import (  # noqa: F401  (re-export: public surface)
    _token_logprob,
    build_block_fn,
    build_step_fn,
    filter_top_k_top_p,
    variant_names,
)
from .engine_spec import SpeculativeMixin, build_spec_rounds
from .engine_types import (  # noqa: F401  (re-export: public surface)
    EngineMetrics,
    Request,
    _pow2_int,
)
from ..ops.expert_ffn import on_kernel_lane
from ..utils import failpoints
from ..utils.anomaly import AnomalyMonitor
from ..utils.flight import FlightRecorder
from ..utils.platform import device_facts
from ..utils.spans import SpanRecorder
from . import kda, mla
from .engine_profiler import EngineProfiler, in_phase
from .moe import STATS, few_tokens
from .transformer import (
    GPTConfig,
    PagedConfig,
    TransformerLM,
    decode_cache_spec,
)


class ServingEngine(
    AdmissionMixin, PagingMixin, KVCacheMixin, HandoffMixin, SpeculativeMixin
):
    """Batch-continuous greedy decoding server (single host, one model).

    ``MAX_BIAS``: per-request logit_bias entries are padded to this fixed
    width so they trace into the jitted step as [slots, MAX_BIAS] arrays
    (no recompiles as biased requests come and go).

    ``cfg`` is the model config WITHOUT paging; the engine derives the
    paged decode config.  ``params`` may be any serving tree the config
    accepts (bf16, or int8 via ``cfg.quant``).
    """

    MAX_BIAS = 16
    # Stop-sequence caps (OpenAI allows 4 stops; 8 is generous).  Checked in
    # submit() so the unauthenticated HTTP path can't make _hit_stop's
    # per-token Python scan unbounded.
    MAX_STOPS = 8
    MAX_STOP_LEN = 32

    def __init__(
        self,
        cfg: GPTConfig,
        params: Any,
        paged: PagedConfig,
        *,
        max_slots: int = 4,
        eos_id: Optional[int] = None,
        prefix_sharing: bool = True,
        rng: Optional[jax.Array] = None,
        metrics: Optional[EngineMetrics] = None,
        spec_gamma: int = 0,
        draft_params: Any = None,
        draft_cfg: Optional[GPTConfig] = None,
        prefill_chunk: Optional[int] = None,
        decode_block: int = 1,
        overlap_steps: int = 1,
        admission: str = "reserve",
        overload=None,
        slo=None,
        kv_retain: bool = False,
        kv_host_cache_mb: float = 0,
        role: str = "unified",
        mesh: Optional[Mesh] = None,
        tp_axis: str = "tp",
        racecheck: bool = False,
        spans: Optional[SpanRecorder] = None,
        flight: Optional[FlightRecorder] = None,
        anomaly: Optional[AnomalyMonitor] = None,
        profiler: Optional[EngineProfiler] = None,
    ):
        if cfg.paged is not None:
            raise ValueError("pass the base config; the engine adds paging")
        if spec_gamma < 0:
            raise ValueError(f"spec_gamma must be >= 0, got {spec_gamma}")
        if decode_block < 1 or (decode_block & (decode_block - 1)):
            # Power of two: the host down-buckets the block to the largest
            # power of two that fits every active slot's remaining budget,
            # so compiled block programs stay O(log decode_block).
            raise ValueError(
                f"decode_block must be a power of two >= 1, got {decode_block}"
            )
        if decode_block > 1 and spec_gamma > 0:
            # Both amortize dispatches over multi-token device rounds with
            # incompatible schedules (scan of exact steps vs draft+verify).
            raise ValueError("decode_block > 1 is not supported with spec_gamma")
        if admission not in ("reserve", "optimistic"):
            raise ValueError(
                f"admission must be 'reserve' or 'optimistic', got {admission!r}"
            )
        if overlap_steps not in (0, 1):
            raise ValueError(
                f"overlap_steps must be 0 or 1, got {overlap_steps}"
            )
        if cfg.lora_serve and spec_gamma > 0:
            # The self-draft is the same model int8-quantized, and quant is
            # mutually exclusive with LoRA (quantize after merging) — there
            # is no coherent draft for a multi-adapter batch.
            raise ValueError("lora_serve is not supported with spec_gamma")
        if prefill_chunk is not None and (
            prefill_chunk < 1 or prefill_chunk & (prefill_chunk - 1)
        ):
            # Power of two so chunks tile every power-of-two length bucket.
            raise ValueError(
                f"prefill_chunk must be a power of two, got {prefill_chunk}"
            )
        self._prefill_chunk = prefill_chunk
        if cfg.mla is not None:
            # What assumed K and V pools, or one attention and one MLP a
            # block, refuses rather than serve wrong numbers (docs/serving.md,
            # "Latent attention and expert layers"): here, in
            # _validate_role, and in the modules themselves (quant, LoRA,
            # use_kernel: raised when the cache tree is traced below).
            what = "latent attention and expert layers (cfg.mla, cfg.moe)"
            if spec_gamma > 0:
                raise ValueError(
                    f"spec_gamma > 0 is not supported with {what}: the self-draft is the "
                    "model int8-quantized, which neither has, and the round programs "
                    "carry no routing counts"
                )
            if mesh is not None and dict(mesh.shape).get(tp_axis, 1) > 1:
                raise ValueError(
                    f"tp={dict(mesh.shape)[tp_axis]} is not supported with {what}: the "
                    "sharding contract (parallel/serving.py) splits pools on a kv-heads "
                    "axis a latent row does not have, and has no rule for experts"
                )
        if spec_gamma > 0:
            # Shared-pool speculation: the draft writes its (approximate)
            # K/V at the frontier and the verify pass overwrites those
            # same positions with exact target K/V before any later read,
            # so the draft needs NO cache of its own — but that only
            # works when both models address the pool identically, i.e.
            # same architecture (self-speculation: the draft is the same
            # model quantized, ops/quant.py).
            if draft_params is None:
                raise ValueError("spec_gamma > 0 requires draft_params")
            if draft_cfg is None:
                draft_cfg = dataclasses.replace(cfg, quant="w8")
            # Only the WEIGHT format may differ: quant_kv is part of the
            # shared pool's storage format (int8 pools + scale pools), so
            # a draft/target mismatch would have the draft writing the
            # wrong dtype into — and reading raw codes out of — the very
            # pages the target owns.
            same = dataclasses.replace(draft_cfg, quant=None) == (
                dataclasses.replace(cfg, quant=None)
            )
            if not same:
                raise ValueError(
                    "engine speculation is shared-pool self-speculation: "
                    "draft_cfg must match the target architecture and "
                    "cache format (only quant may differ)"
                )
        self._spec_gamma = spec_gamma
        self.draft_params = draft_params
        self.paged = paged
        self.cfg = dataclasses.replace(cfg, paged=paged)
        # Dense prefill bridge shares max_seq with the paged logical view.
        self.dense_cfg = dataclasses.replace(cfg, paged=None, max_seq=paged.max_len)
        self.params = params
        self.max_slots = max_slots
        self.eos_id = eos_id

        # Tensor parallelism (ISSUE 6): an explicit sharding contract for
        # the whole engine state dict (parallel/serving.py) over a 1-axis
        # ``tp`` mesh — normally built from the chips the plugin
        # allocated (parallel/mesh.mesh_from_allocation).  Params follow
        # the Megatron path rules (parallel/tensor.py), KV pools split on
        # the kv-heads axis, page tables / seq_lens / the step dict
        # replicate.  Placement happens HERE and on every _dev=None
        # rebuild (_rep), never implicitly: a rebuild that re-derived
        # placement per leaf would reshard multi-MB pools mid-serve.
        self.mesh = mesh
        self._tp_axis = tp_axis
        self.tp_size = 1
        self._rep_sharding: Optional[NamedSharding] = None
        if mesh is not None:
            axes = dict(mesh.shape)
            if tp_axis not in axes:
                raise ValueError(
                    f"engine mesh has no {tp_axis!r} axis (axes: {axes})"
                )
            self.tp_size = axes[tp_axis]
            if self.tp_size > 1 and cfg.kv_heads % self.tp_size:
                raise ValueError(
                    f"tp={self.tp_size} does not divide "
                    f"num_kv_heads={cfg.kv_heads}: KV pools shard on the "
                    "kv-heads axis — pick a tp degree dividing the kv "
                    "head count (or a config with more kv heads)"
                )
            from ..parallel.tensor import tp_param_sharding

            self._rep_sharding = NamedSharding(mesh, PartitionSpec())
            self.params = jax.device_put(
                params, tp_param_sharding(params, mesh, tp_axis)
            )
            if draft_params is not None:
                self.draft_params = jax.device_put(
                    draft_params, tp_param_sharding(draft_params, mesh, tp_axis)
                )

        model = TransformerLM(self.cfg, decode=True)
        spec = decode_cache_spec(model, max_slots)
        self.cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        # Per-slot recurrent state beside the pages (a mixer's or a
        # linear attention's ``slot_*`` leaves, models/ssm.py and
        # models/kda.py; 0 for a model without one).
        # The compiled cache writers carry it through graft and teardown
        # (engine_paging.py); every path that skips or reorders the work
        # that BUILDS it refuses below or falls back (restore-resume:
        # engine_kvcache.py; split roles: engine_handoff.py).
        self.slot_state_bytes = slot_state_bytes(self.cache)
        self.cache_bytes_per_token, self.cache_pad_bytes_per_token = cache_bytes_per_token(
            self.cache, cfg.mla.row_width if cfg.mla is not None else None
        )
        if self.slot_state_bytes and spec_gamma > 0:
            raise ValueError(
                "spec_gamma > 0 is not supported on a model with per-slot "
                "recurrent state (cfg.mixer, cfg.kda): a rejected draft would "
                "have to roll the state back, and the round programs do not"
            )
        if self.slot_state_bytes and self.tp_size > 1:
            raise ValueError(
                f"tp={self.tp_size} is not supported on a model with "
                "per-slot recurrent state (cfg.mixer, cfg.kda): the sharding "
                "contract (parallel/serving.py) has no rule for slot_* leaves"
            )
        if mesh is not None:
            from ..parallel.serving import cache_sharding

            self.cache = jax.device_put(
                self.cache, cache_sharding(self.cache, mesh, tp_axis)
            )
        # The layers that hold pages (an ``attn`` subtree): every walker of
        # pools, tables and lengths goes by these.  A linear-attention
        # layer (models/kda.py) holds per-slot state and no pages.
        self._layer_names = [f"layer_{i}" for i in range(cfg.num_layers) if not cfg.linear_layer(i)]
        if not self._layer_names:
            raise ValueError(
                "the paged engine needs one layer with attention: cfg.kda with no "
                "attention_layers holds no pages for the scheduler to allocate"
            )
        self._linear_layers = cfg.num_layers - len(self._layer_names)

        # Single-token decode steps are built lazily per (filtered,
        # want_lp) — like _block_fn — so the common greedy/temperature
        # path never compiles the top-k/top-p sort and never computes the
        # [slots, vocab] log-softmax that only logprobs requests read
        # (jit programs compile on first use: a variant that is never
        # requested costs nothing).
        #
        # The cache is donated: the engine reassigns self.cache from the
        # step's output, so the input pool buffers are dead the moment the
        # call is issued — without donation every step transiently holds
        # TWO copies of every layer's page pool in HBM (a pool sized near
        # HBM capacity would OOM at the first step) and pays a pool-sized
        # copy.  Host-side .at[slot].set bookkeeping always runs on the
        # returned tree, never the donated argument.
        self._step_fns: dict = {}
        # Decode blocks (decode_block > 1): when the engine is in pure
        # decode — no admission work, every slot past prefill — the host
        # dispatches ONE program that scans T exact single-token steps
        # (same math, T fresh subkeys), then consumes/rewinds on sync.
        # Each dispatch costs one host round-trip instead of T, which is
        # the serving bottleneck at small batch.  Jitted
        # per (T, filtered) lazily; T down-buckets by powers of two so at
        # most O(log decode_block) programs ever compile.
        self._decode_block = decode_block
        self._decode_model = model
        self._block_fns: dict = {}
        # ALL prefill runs through the multi-token CACHED append (the
        # speculative verifier's path): each chunk attends against the
        # K/V of every previous chunk via position masks, so a prompt can
        # be consumed across several bounded dispatches — or one.  One
        # model per LENGTH BUCKET: the throwaway dense cache is sized to
        # the bucket, not paged.max_len, so a short prompt's chunks score
        # [chunk, bucket] instead of [chunk, max_len] — up to
        # max_len/bucket x less prefill attention work in long-context
        # engines (positions past the bucket were masked anyway, so
        # outputs are identical).
        self._dense_chunk_models: dict[int, TransformerLM] = {}

        if spec_gamma > 0:
            draft_model = TransformerLM(
                dataclasses.replace(draft_cfg, paged=paged), decode=True
            )
            self._spec_round, self._spec_round_plain = build_spec_rounds(
                model, draft_model, self._layer_names, spec_gamma
            )
        # Host-visible speculation counters (also exported via metrics):
        # acceptance rate = accepted / proposed, the gamma-tuning signal.
        self.spec_proposed = 0
        self.spec_accepted = 0
        # Optimistic admission: allocate prompt pages only at admission and
        # grow generation pages on demand; a pool shortage preempts the
        # NEWEST ready slot (recompute-resume via the effective prompt).
        self._optimistic = admission == "optimistic"
        self.preemptions = 0
        self._seq_counter = 0
        # Set by each _admit pass; read by the decode-block gate.
        self._admit_page_blocked = False

        # In-program table derivation (non-speculative engines): the full
        # allocated page chain lives in ONE [slots, max_pages_per_seq]
        # device array, and the jitted step computes the visible prefix
        # from it (engine_sampling._derived_tables) — no per-layer host
        # publication scatters, and graft/teardown/reclaim edit one array
        # instead of num_layers cache tables.  Speculative engines keep
        # host-published cache tables (their round programs read the
        # table as carried cache state).
        self._derive_tables = spec_gamma == 0
        self._chain = self._rep(
            jnp.zeros((max_slots, paged.max_pages_per_seq), jnp.int32)
        )
        # Page 0 is the idle-slot scratch target — never allocated.
        self.free_pages: deque[int] = deque(range(1, paged.num_pages))  # guarded by: _lock
        self.slots: list[Optional[Request]] = [None] * max_slots  # guarded by: _lock
        self._slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
        self._slot_last: list[int] = [0] * max_slots  # last emitted token
        self._slot_len: list[int] = [0] * max_slots  # consumed positions
        self._slot_temp: list[float] = [0.0] * max_slots  # 0 = greedy
        # Per-slot adapter id (-1 = base model); traced into the step so
        # slots switch adapters with no recompile (multi-LoRA serving).
        self._slot_aid: list[int] = [-1] * max_slots
        # Per-slot sampler restrictions; vocab / 1.0 mean "off" so idle
        # slots are no-ops in the shared filter.
        self._slot_topk: list[int] = [cfg.vocab_size] * max_slots
        self._slot_topp: list[float] = [1.0] * max_slots
        # Per-slot sparse logit bias: up to MAX_BIAS (id, value) pairs,
        # padded with (0, 0.0) — a zero bias is a no-op whatever the id.
        self._slot_bias_ids: list[list[int]] = [
            [0] * self.MAX_BIAS for _ in range(max_slots)
        ]
        self._slot_bias_vals: list[list[float]] = [
            [0.0] * self.MAX_BIAS for _ in range(max_slots)
        ]
        # Logical index of _slot_pages[s][0] in the device table row (> 0
        # once leading pages were reclaimed by a sliding window).
        self._slot_page_base: list[int] = [0] * max_slots
        # Logical page count PUBLISHED to the device table per slot.  The
        # full allocated chain includes not-yet-written generation pages;
        # publishing those at admission would make the kernel's pipeline
        # fetch them every step (pl.when gates compute, not the block
        # copies), so table entries stay at scratch page 0 until the write
        # frontier reaches them — per-row traffic is O(len), not
        # O(allocated).
        self._slot_visible: list[int] = [0] * max_slots
        self._slot_seq: list[int] = [0] * max_slots
        # A reserved slot decodes only after its prefill job grafted it
        # (chunked prefill spans several step() calls; until ready the
        # slot behaves exactly like an idle one in the jitted step).
        self._slot_ready: list[bool] = [False] * max_slots
        self._pending: list[dict] = []  # in-flight prefill jobs
        # Private pages of not-yet-grafted requests: the prefix-sharing
        # match refuses them (see _match_prefix) until _activate removes
        # them post-graft.
        self._pending_pages: set[int] = set()
        self.queue: deque[Request] = deque()  # guarded by: _lock
        # submit() is documented callable from other threads (the serving
        # topology: an RPC handler enqueues while the owner thread loops
        # step(), and MetricsServer scrapes concurrently) — the queue and
        # gauge updates are the shared state, so both sides take this lock.
        # Reentrant: submit() updates gauges while already holding it.
        self._lock = threading.RLock()
        self._next_rid = 0
        self._prefill_cache: dict[int, Any] = {}
        # The compiled makers of a prefill job's zero dense cache by
        # (bucket, batch) (engine_admission._zero_prefill_cache), the
        # jobs started and the makers' dispatches: one a job.
        self._prefill_cache_makers: dict[tuple[int, int], Any] = {}
        self.prefill_jobs = 0
        self.prefill_cache_dispatches = 0
        # Compiled writers into the device cache tree
        # (engine_paging._cache_write) and their dispatches by operation.
        self._cache_writers: dict[tuple, Any] = {}
        self.cache_write_dispatches = {"graft": 0, "slot": 0}
        # The chain writer's dispatches (one a frontier pass that grew a
        # page, optimistic admission) and the pages they published.
        self.chain_write_dispatches = 0
        self.chain_pages_written = 0
        self._rng = self._rep(jax.random.PRNGKey(0) if rng is None else rng)
        # Device-resident step state: the per-slot arrays the jitted step
        # consumes (tokens/positions/temps/aids/filters/biases/key) live
        # on device between steps, with tokens/positions/key fed forward
        # from the previous step's OUTPUTS.  Rebuilt from the host lists
        # only when slot structure changes (_mark_state_dirty: admission,
        # teardown, speculative rounds) — in steady-state decode a step
        # costs ZERO host->device uploads and no separate key-split
        # dispatch, which is what matters on a real TPU VM where device
        # step time (~100us) is comparable to one transfer.
        self._dev: Optional[dict] = None
        # Overlapped decode pipeline: with overlap_steps == 1 the loop
        # dispatches step N+1 from the fed-forward device state BEFORE
        # consuming step N's readback, so per-token host work (EOS/stop
        # checks, frontier extension, metrics) executes while the
        # accelerator computes the next step instead of idling through
        # it.  ``_inflight`` holds the pending dispatch's record; its
        # validity token is the identity of the device-state dict it fed
        # forward (any _mark_state_dirty breaks it — see _take_inflight).
        # Speculative engines never overlap: a round's host consumption
        # DECIDES the next dispatch's inputs (data-dependent acceptance),
        # so there is nothing to dispatch ahead.
        # Nor do engines whose slots hold recurrent state: a discarded
        # dispatch has already advanced every surviving slot's state
        # (K/V writes are idempotent on re-dispatch, a recurrence is
        # not), and nothing rolls it back.
        self._overlap_steps = (
            0 if spec_gamma or self.slot_state_bytes else overlap_steps
        )
        self._inflight: Optional[dict] = None
        self.overlap_hits = 0
        self.overlap_discards = 0
        self._inflight_guard = None
        self.metrics = metrics
        if metrics:
            metrics.tp_size.set(self.tp_size)
            metrics.slot_state_bytes.set(self.slot_state_bytes)
            metrics.cache_bytes_per_token.set(self.cache_bytes_per_token)
            metrics.cache_pad_bytes_per_token.set(self.cache_pad_bytes_per_token)
        # Routing counts of a model with expert layers (models/moe.py),
        # summed on the host from what the decode programs pack behind
        # their tokens and the prefill chunks hand back (_moe_fold): rows
        # are the expert layers, columns moe.STATS then a count per held
        # expert.  None for a model without them.
        self._moe_shape = None
        if cfg.moe is not None:
            self._moe_shape = (cfg.expert_layers, cfg.moe.stats_width)
            # Whether a decode step's experts run the grouped-FFN kernel
            # is a fact of the compiled program: the backend's lane and
            # the step's row count (one row a slot).
            self.moe_expert_kernel = on_kernel_lane() and few_tokens(self.max_slots)
            self.moe_counts = {
                phase: np.zeros(self._moe_shape, np.int64) for phase in ("decode", "prefill")
            }
            if metrics:
                # The series exist from the start: a scrape reads 0, not nothing.
                self._moe_fold("decode", np.zeros(self._moe_shape, np.int64))
        # Whether a decode step's latent attentions read the pool through
        # the latent kernel (ops/latent_attention.py) is a fact of the
        # compiled program too: the backend's lane, one query a row.
        self.mla_latent_kernel = cfg.mla is not None and mla.latent_kernel_lane(1)
        # And whether a decode step's linear attentions run the recurrence
        # kernel (ops/kda_decode.py): the same facts.
        self.kda_kernel = bool(self._linear_layers) and kda.kernel_lane(1)
        # Forensics layer (always on — a production incident cannot ask
        # for instrumentation retroactively, and all three pieces are
        # stdlib-cheap): a bounded flight-recorder black box of typed
        # events, an EWMA anomaly monitor emitting incident records with
        # the surrounding flight window attached (GET /debug/incidents),
        # and a per-step phase profiler (GET /debug/profile).  Callers
        # may pass shared/preconfigured instances (the serving main
        # registers the flight box for SIGUSR2 dumps).
        self.flight = (
            flight
            if flight is not None
            else FlightRecorder(capacity=1024, name="engine")
        )
        if anomaly is None:
            anomaly = AnomalyMonitor(
                flight=self.flight,
                on_incident=(
                    (lambda m: metrics.incidents.inc(metric=m))
                    if metrics
                    else None
                ),
            )
        self.anomaly = anomaly
        # configure() is get-or-create: a caller-preconfigured monitor
        # keeps its thresholds.  Step time warms over ~2 windows of
        # steady decode; one-sided high (fast steps are never incidents).
        self.anomaly.configure(
            "engine.step_seconds", warmup=50, z_threshold=6.0, sustain=3
        )
        self.anomaly.configure(
            "engine.ttft_seconds", warmup=20, z_threshold=6.0, sustain=2
        )
        # Split-K paged-attention kernel routing (ops/paged_attention.py,
        # ops/tuning.py): resolve the config's tri-state ONCE, export it,
        # and surface the two ctor-time fallback decisions an operator
        # would otherwise discover in a profile — a kernel-on spec engine
        # still gathers for its multi-token verify pass (single-token
        # draft/decode steps keep the kernel), and a kernel-on engine on
        # an unswept TPU generation runs the conservative fallback split
        # row until a hardware round records a real one.
        self._device_facts = device_facts()  # fixed for the process's life
        self.kernel_on = paged.kernel_enabled(cfg.quant_kv)
        if metrics:
            metrics.kernel_enabled.set(int(self.kernel_on))
        if self.kernel_on:
            from ..ops import tuning as _kernel_tuning

            fallback = None
            if spec_gamma > 0:
                fallback = "spec_verify"
            elif (
                jax.default_backend() == "tpu"
                and not _kernel_tuning.has_row()
            ):
                fallback = "untuned_generation"
            if fallback is not None:
                if metrics:
                    metrics.kernel_fallbacks.inc(reason=fallback)
                self.flight.record(
                    "kernel.fallback",
                    reason=fallback,
                    generation=_kernel_tuning.device_generation(),
                    splits=paged.kernel_num_splits,
                )
        self.profiler = (
            profiler
            if profiler is not None
            else EngineProfiler(
                flight=self.flight,
                observe_step=lambda s: self.anomaly.observe(
                    "engine.step_seconds", s
                ),
                seconds=metrics.loop_seconds if metrics else None,
                counts=metrics.loop_counts if metrics else None,
                host_cpu=metrics.loop_host_cpu if metrics else None,
            )
        )
        self._step_tokens = 0  # tokens emitted by the step in flight
        # Hung-step watchdog (models/engine_watchdog.py), installed by
        # the serving server (EngineServer wires it to its fence path).
        # The engine only feeds it: step start/finish stamps plus grace
        # marks on legitimately-slow events (new jitted program built,
        # prefill advanced, admission activated) so first-shape compiles
        # never false-trip.  None = off, zero cost.
        self.watchdog = None
        # Overload control (models/engine_overload.py): deadline expiry,
        # priority + per-tenant-fair admission order, and the AIMD
        # concurrency limiter.  Library default OFF (``overload=None`` —
        # the queue stays strictly FIFO and streams are bit-identical to
        # every prior round); the serving CLIs default it ON, matching
        # the kv-retain convention.  Pass True for the default config or
        # an OverloadConfig for tuned thresholds.
        self.overload = None
        if overload:
            from .engine_overload import OverloadConfig, OverloadController

            self.overload = OverloadController(
                max_slots,
                overload if isinstance(overload, OverloadConfig) else None,
                metrics=metrics,
                flight=self.flight,
            )
        # SLO accounting (utils/slo.py, ISSUE 16): per-request SLI
        # verdicts (TTFT / per-request ITL p99 / availability) into
        # sliding-window error budgets, plus per-tenant usage meters.
        # Library default OFF like overload (``slo=None`` — zero cost);
        # the serving CLIs default it ON.  Pass True for the default
        # objectives, a dict of threshold overrides
        # (``{"ttft_target_s": ..., "itl_p99_target_s": ...}``), or a
        # prebuilt SLOTracker.  Both mutate only under the engine lock.
        self.slo = None
        self.usage = None
        if slo:
            from ..utils.slo import SLOTracker, UsageMeter, default_objectives

            if isinstance(slo, SLOTracker):
                self.slo = slo
            elif isinstance(slo, dict):
                self.slo = SLOTracker(objectives=default_objectives(**slo))
            else:
                self.slo = SLOTracker()
            self.usage = UsageMeter()
        # Request-scoped tracing (utils/spans.py): None = off, zero cost.
        # Per-slot monotonic stamp of the slot's last emitted token — the
        # inter-token-latency anchor (reset at activation and teardown).
        self.spans = spans
        self._slot_emit_t: list[float] = [0.0] * max_slots
        # Prefix sharing: K/V are a deterministic function of (params,
        # prompt tokens), so FULL pages covering a common prompt prefix are
        # byte-identical across requests and can be shared read-only —
        # decode only ever writes at the growing frontier, which lives in a
        # private page.  The registry is a per-page trie keyed
        # (parent_page, page_chunk) — O(prompt) to match/register, vs
        # O(prompt²/page_size) for whole-prefix keys — with -1 as the root
        # parent.  Pages are refcounted and registry links die with their
        # last user (this serves the concurrent shared-system-prompt case,
        # not a persistent prompt cache; freed-parent links cannot go
        # stale: any sequence holding a child page holds its whole prefix
        # chain, so a child always dies no later than its parent).
        self.prefix_sharing = prefix_sharing
        self._page_refs: dict[int, int] = {}
        self._prefix_pages: dict[tuple[int, tuple], int] = {}
        self._page_keys: dict[int, list[tuple[int, tuple]]] = {}
        # Keys in which a page is the PARENT: windowed reclamation can free
        # a parent before its children, and a freed id may be reallocated
        # and re-registered with different content — surviving child links
        # would then form a stale chain, so they die with the parent.
        self._child_keys: dict[int, list[tuple[int, tuple]]] = {}
        # Trie mutation counter (register/teardown bump it): the fabric
        # digest cache (engine_handoff.py) keys on this + the arena
        # version so an unchanged trie never rebuilds the bloom.
        self._trie_version = 0  # guarded by: _lock
        # KV cache tiering (engine_kvcache.py): with kv_retain, a
        # prefix-registered page whose refcount hits zero is RETAINED
        # (trie links live, reclaimed lazily under pool pressure)
        # instead of freed, and kv_host_cache_mb > 0 adds the bounded
        # host-RAM arena that reclaimed pages and preemption snapshots
        # spill into — repeated prefixes and preemption resumes then
        # restore instead of recomputing.  Library default OFF (the
        # exact-pool accounting other subsystems and tests rely on);
        # the serving CLIs default it ON.
        self._init_kvcache(kv_retain, kv_host_cache_mb)
        # Disaggregated prefill/decode roles (models/engine_handoff.py):
        # "unified" (default) is today's engine byte-for-byte; "prefill"
        # serves POST /v1/prefill probes and publishes finished pages
        # into the content-addressed arena; "decode" restores handed-off
        # prefixes and SKIPS the prefill chunks they cover.
        self._init_handoff(role)
        if racecheck:
            # Lock-discipline detection (utils/racecheck.py): every
            # mutation of the cross-thread state must hold the engine
            # lock, and with this flag a violation RAISES at the faulty
            # call site instead of corrupting state probabilistically.
            # The stress suites run with it on; production engines skip
            # the per-op check.
            from ..utils.racecheck import GuardedDeque, GuardedDict, OwnerGuard

            # The in-flight overlap record is owner-thread-only by
            # contract (the step loop dispatches and consumes it while
            # submit/cancel mutate slots under the lock); the guard
            # raises if any other thread touches the handoff off-lock.
            self._inflight_guard = OwnerGuard(
                lock=self._lock, name="_inflight"
            )
            self.free_pages = GuardedDeque(
                self.free_pages, lock=self._lock, name="free_pages"
            )
            self.queue = GuardedDeque(
                self.queue, lock=self._lock, name="queue"
            )
            self._page_refs = GuardedDict(
                self._page_refs, lock=self._lock, name="_page_refs"
            )

    def _dense_chunk_model(self, bucket: int) -> TransformerLM:
        """The cached-append prefill model for one length bucket (cache
        sized to the bucket; see __init__ note).  Cached per bucket —
        O(log max_len) instances ever exist."""
        model = self._dense_chunk_models.get(bucket)
        if model is None:
            self._wd_grace(f"compile:prefill_bucket_{bucket}")
            model = TransformerLM(
                dataclasses.replace(self.dense_cfg, max_seq=bucket),
                decode=True,
                append_mode="cached",
            )
            self._dense_chunk_models[bucket] = model
        return model

    def _wd_grace(self, reason: str) -> None:
        """Mark the in-flight step as legitimately slow for the hung-step
        watchdog (a fresh XLA compile or admission/prefill work may run
        orders of magnitude past the decode baseline).  No-op without a
        watchdog installed."""
        if self.watchdog is not None:
            self.watchdog.note_grace(reason)

    # ----------------------------------------------------------------- steps

    def _rep(self, x):
        """Place one host-built array REPLICATED on the engine mesh
        (identity off-mesh).  Every fresh device array the host feeds the
        jitted step — state rebuilds, seq_lens realigns, the PRNG key —
        goes through here, so a ``_dev=None`` rebuild re-applies the
        sharding contract instead of re-deriving placement (an unplaced
        single-device array under a donated sharded step would reshard
        every dispatch)."""
        if self._rep_sharding is None:
            return x
        return jax.device_put(x, self._rep_sharding)

    def assert_sharded(self) -> int:
        """Sharding-coverage lint (parallel/serving.py): every leaf of
        the engine state dict — params, cache, chain, and the
        device-resident step dict when built — must carry an explicit
        placement on the engine mesh, and KV pools must actually be
        partitioned (no silent replication of multi-MB pools).  Raises
        AssertionError naming the offending path; returns the leaf count
        checked.  Meaningless without a mesh."""
        if self.mesh is None:
            raise ValueError(
                "engine has no mesh: build it with mesh= to lint sharding"
            )
        from ..parallel.serving import assert_explicit_sharding

        tree: dict = {
            "params": self.params,
            "cache": self.cache,
            "chain": self._chain,
            "rng": self._rng,
        }
        if self._dev is not None:
            tree["dev"] = {
                k: v for k, v in self._dev.items() if isinstance(v, jax.Array)
            }
        return assert_explicit_sharding(
            tree, self.mesh, tp_axis=self._tp_axis
        )

    def _mark_state_dirty(self) -> None:
        """Invalidate the device-resident step state: the next dispatch
        rebuilds every per-slot array from the host lists.  Called on any
        event that changes a slot's scalars (activation, teardown) or
        moves lengths by a data-dependent amount (speculative rounds)."""
        self._dev = None

    def _device_state(self) -> dict:
        """The per-slot arrays the next dispatch consumes, on device.
        Fresh-built from host truth when dirty; otherwise whatever the
        previous step fed forward (tokens/positions/key) plus the cached
        uploads (temps/aids/filters/biases, which only change via dirty
        events)."""
        if self._dev is None:
            return self._rebuild_device_state()
        return self._dev

    @in_phase("dispatch.state")
    def _rebuild_device_state(self) -> dict:
        """Build the per-slot step state from the host lists: four
        uploads, a key split and the variant flags."""
        if self.metrics:
            self.metrics.state_rebuilds.inc()
        self._rng, sub = jax.random.split(self._rng)
        # _rep: the rebuild re-applies the sharding contract (mesh
        # engines replicate these per-slot vectors explicitly; the
        # no-mesh path is identity).
        dev = self._dev = {
            "tokens": self._rep(
                jnp.asarray(self._slot_last, jnp.int32)[:, None]
            ),
            "positions": self._rep(
                jnp.asarray(self._slot_len, jnp.int32)[:, None]
            ),
            "temps": self._rep(jnp.asarray(self._slot_temp, jnp.float32)),
            "aids": self._rep(jnp.asarray(self._slot_aid, jnp.int32)),
            "key": self._rep(sub),
        }
        # Step-variant selector flags ride the state dict: they are a
        # function of the occupied slots' sampler settings, which only
        # ever change through an activation/teardown — events that
        # dirty the whole state — so ONE slot scan per rebuild
        # replaces three full-slot scans per step in the hot loop.
        filtered = want_lp = biased = False
        for s in range(self.max_slots):
            req = self.slots[s]
            if req is None:
                continue
            if (
                self._slot_topk[s] < self.cfg.vocab_size
                or self._slot_topp[s] < 1.0
            ):
                filtered = True
            if req.logprobs:
                want_lp = True
            if req.logit_bias:
                biased = True
        dev["filtered"] = filtered
        dev["want_lp"] = want_lp
        dev["biased"] = biased
        return dev

    def _feed_forward(self, dev: dict, tokens, positions, key) -> dict:
        """Install the step's returned next-inputs as the new device
        state (flags and cached variant arrays carry over).  Runs BEFORE
        host consumption: a finish in consumption tears the slot down
        through _clear_slot, which marks the state dirty again —
        ordering keeps both paths correct.  Returns the installed dict
        (the overlap pipeline's in-flight validity token)."""
        self._dev = {
            **dev, "tokens": tokens, "positions": positions, "key": key,
        }
        return self._dev

    def _variant_arrays(self, dev: dict, filtered: bool, biased: bool) -> list:
        """The optional per-slot arrays matching
        engine_sampling.variant_names.  Built lazily into the device
        state on first need: a greedy-only server rebuilds its state on
        every admission/finish, and uploading filter/bias arrays no
        compiled variant consumes would defeat the variant-signature
        split (engine_sampling.py).  Safe to cache: any change to a
        slot's sampler settings rides an activation/teardown, which
        marks the whole state dirty."""
        arrays = []
        if filtered:
            if "topks" not in dev:
                dev["topks"] = self._rep(
                    jnp.asarray(self._slot_topk, jnp.int32)
                )
                dev["topps"] = self._rep(
                    jnp.asarray(self._slot_topp, jnp.float32)
                )
            arrays += [dev["topks"], dev["topps"]]
        if biased:
            if "bias_ids" not in dev:
                dev["bias_ids"] = self._rep(
                    jnp.asarray(self._slot_bias_ids, jnp.int32)
                )
                dev["bias_vals"] = self._rep(
                    jnp.asarray(self._slot_bias_vals, jnp.float32)
                )
            arrays += [dev["bias_ids"], dev["bias_vals"]]
        return arrays

    def _step_fn(self, filtered: bool, want_lp: bool, biased: bool = False):
        """The jitted single-token decode step, built lazily once per
        (filtered, want_lp, biased) — engine_sampling.build_step_fn —
        and cached on THIS instance (a process-global cache would pin
        params/pools beyond the engine's lifetime)."""
        key_ = (filtered, want_lp, biased)
        if key_ not in self._step_fns:
            self._wd_grace("compile:step")
            self._step_fns[key_] = build_step_fn(
                self._decode_model, filtered, want_lp, biased,
                derive_tables=self._derive_tables,
            )
        return self._step_fns[key_]

    def _block_fn(self, T: int, filtered: bool, want_lp: bool, biased: bool = False):
        """The jitted T-step decode block, built lazily once per
        (T, filtered, want_lp, biased) — engine_sampling.build_block_fn."""
        key_ = (T, filtered, want_lp, biased)
        if key_ not in self._block_fns:
            self._wd_grace(f"compile:block_{T}")
            self._block_fns[key_] = build_block_fn(
                self._decode_model, T, filtered, want_lp, biased,
                derive_tables=self._derive_tables,
            )
        return self._block_fns[key_]

    def _chain_args(self) -> list:
        """The chain operand for derive-tables step variants (leading
        entry of the *rest signature; empty for speculative engines)."""
        return [self._chain] if self._derive_tables else []

    # --------------------------------------------- overlapped decode pipeline
    #
    # The loop's split dispatch/consume halves.  State machine, per
    # step() call on the decode path:
    #
    #   no in-flight   -> dispatch N; if overlap allowed, dispatch N+1
    #                     from N's fed-forward state; consume N.
    #   valid in-flight-> dispatch N+1 from its fed-forward state FIRST
    #                     (keep the device busy), then consume N while
    #                     N+1 computes (the host_gap profiler phase).
    #   stale in-flight-> discard (one wasted lane): any event that calls
    #                     _mark_state_dirty (admission, finish, cancel,
    #                     preemption, spec round) invalidated the inputs
    #                     it was dispatched from.  A torn-down slot
    #                     already behaves as idle in the jitted step and
    #                     discarded K/V writes are overwritten before any
    #                     masked read can see them, so the only device
    #                     state a discard must repair is seq_lens (the
    #                     paged append writes at the CARRIED seq_lens,
    #                     not the traced positions) — one vector write
    #                     per layer back to host truth.

    def _overlap_allowed(self) -> bool:
        """Whether dispatching one decode round ahead of host consumption
        pays off right now.  Overlap is guaranteed-wasted work whenever
        the queue head could actually admit this step (the activation
        would invalidate the in-flight dispatch — the same reasoning as
        the decode-block gate) or while a chunked prefill is streaming
        in (its activation lands within a few steps), so those degrade
        to the synchronous loop."""
        if not self._overlap_steps or self._pending:
            return False
        return (
            not self.queue
            or all(s is not None for s in self.slots)
            or self._admit_page_blocked
        )

    def _guard_inflight(self, op: str) -> None:
        if self._inflight_guard is not None:
            self._inflight_guard.check(op)

    def _dispatch_decode(self, active: list[int], T: int = 1) -> dict:
        """Enqueue one decode dispatch (a single step, or a T-step block)
        from the current device state and install its fed-forward outputs
        as the new state.  Returns the record consumption needs: the
        packed readback handle, the want_lp flag it was compiled with,
        and (slot, request) pairs pinned at dispatch time so a consumer
        can skip lanes whose slot was evicted between dispatch and sync.
        ``dev`` in the record is the state dict this dispatch installed —
        identity-compared against self._dev at consume time, which makes
        it the in-flight validity token (every _mark_state_dirty breaks
        the identity)."""
        self._guard_inflight("dispatch")
        dev = self._device_state()
        filtered, want_lp, biased = (
            dev["filtered"], dev["want_lp"], dev["biased"],
        )
        fn = (
            self._step_fn(filtered, want_lp, biased)
            if T == 1
            else self._block_fn(T, filtered, want_lp, biased)
        )
        if self.metrics:
            (
                self.metrics.decode_dispatches_step
                if T == 1
                else self.metrics.decode_dispatches_block
            ).inc()
        args = (
            self.params, self.cache, dev["tokens"], dev["positions"],
            dev["temps"], dev["aids"], dev["key"],
            *self._chain_args(),
            *self._variant_arrays(dev, filtered, biased),
        )
        with self.profiler.phase("dispatch.enqueue"):
            out, ff_tok, ff_pos, ff_key, self.cache = fn(*args)
        return {
            "T": T,
            "out": out,
            "want_lp": want_lp,
            "moe_shape": self._moe_shape,
            # The readback's shape before the routing counts were packed
            # behind it (engine_sampling.pack_stats).
            "out_shape": (2,) * want_lp + (self.max_slots,) + (T,) * (T > 1),
            "active": list(active),
            "reqs": [self.slots[s] for s in active],
            "dev": self._feed_forward(dev, ff_tok, ff_pos, ff_key),
        }

    def _take_inflight(self, T: int) -> Optional[dict]:
        """Pop the in-flight record if it is still consumable: nothing
        invalidated the device state it fed forward (dev identity) and
        the loop is consuming the same dispatch shape it carries (T).
        Anything else discards it — one wasted lane."""
        inflight = self._inflight
        if inflight is None:
            return None
        self._guard_inflight("consume")
        self._inflight = None
        if self._dev is None or inflight["dev"] is not self._dev:
            self._discard(inflight, "state_dirty")
            return None
        if inflight["T"] != T:
            self._discard(inflight, "shape_switch")
            return None
        return inflight

    def _discard(self, inflight: dict, reason: str) -> None:
        """Throw away an overlapped dispatch.  Its K/V writes are
        harmless (a torn-down slot's pages are overwritten by the next
        owner before any visible read; a surviving slot's re-dispatch
        overwrites position L with identical rows), but the dispatch
        advanced every row's carried seq_lens past host truth — re-align
        them.  The fed-forward state derives from outputs the host never
        consumed, so it is dropped too: the next dispatch rebuilds from
        the host lists."""
        self._dev = None
        self._realign_seq_lens()
        self.overlap_discards += 1
        if self.metrics:
            self.metrics.overlap_discards.inc()
        if self.flight is not None:
            self.flight.record(
                "overlap.discard",
                reason=reason,
                T=inflight["T"],
                slots=len(inflight["active"]),
            )

    @in_phase("finish.realign")
    def _realign_seq_lens(self) -> None:
        """Set every attention layer's device lengths to host truth, in
        one vector write per layer.  A FRESH array per layer: sharing
        one would hand the next dispatch's donation the same buffer
        twice, which XLA rejects (see the identical note in _spec_step)."""
        for name in self._layer_names:
            att = self.cache[name]["attn"]
            self.cache[name]["attn"] = {
                **att,
                "seq_lens": self._rep(jnp.array(self._slot_len, jnp.int32)),
            }

    def _drop_stale_inflight(self, reason: str) -> None:
        """Discard the pending overlap dispatch (if any) after a dirty
        event that its consumption itself caused (finish/cancel found
        during consume)."""
        inflight, self._inflight = self._inflight, None
        if inflight is not None:
            self._guard_inflight("discard")
            self._discard(inflight, reason)

    @staticmethod
    def _unpack(rec: dict):
        """Split a record's packed device→host readback (ONE transfer —
        engine_sampling packs tokens with logprobs as float32 rows when
        a slot asked, and ships the token vector alone otherwise)."""
        # Chaos seam (docs/chaos.md): delay stalls the readback sync —
        # the injected step-time blowup the engine.step_seconds anomaly
        # detector must catch; error escapes step() and kills the owner
        # loop (the engine-death shape: /healthz flips 503); corrupt
        # flips bytes of the synced token buffer IN PLACE — the stream
        # keeps flowing with wrong tokens, the silent-data-corruption
        # ground truth the canary prober's bit-exactness verdict is
        # scored against.  Disarmed cost is one dict truthiness check
        # per step.
        hit = failpoints.fire("engine.readback")
        arr = np.asarray(rec["out"])
        if rec.get("moe_shape"):
            n = rec["moe_shape"][0] * rec["moe_shape"][1]
            rec["moe_stats"] = arr[-n:].astype(np.int64).reshape(rec["moe_shape"])
            arr = arr[:-n].reshape(rec["out_shape"])
        if rec["want_lp"]:
            toks, lps = arr[0].astype(np.int64), arr[1]
        else:
            toks, lps = arr, None
        if hit is not None and hit.mode == "corrupt":
            # Flip nbytes low-order bytes of the token buffer (int64
            # little-endian: byte 0 is token 0's LSB, so 1 byte = one
            # off-by-one wrong token) — applied AFTER any logprob
            # unpack so the flip always lands on token integers, never
            # rounds away in a float conversion.
            nbytes = int(hit.arg) if hit.arg else 1
            toks = np.array(toks, dtype=np.int64)
            flat = toks.view(np.uint8).reshape(-1)
            flat[: max(1, min(nbytes, flat.size))] ^= 0x01
        return toks, lps

    def _moe_fold(self, phase: str, stats) -> None:
        """Add one dispatch's routing counts ([expert layers, stats],
        already on the host) to the engine's sums and counters.  A decode
        dispatch that was discarded unread is not counted, like its
        tokens; a finished slot's tail iterations inside a block are."""
        if stats is None:
            return
        self.moe_counts[phase] += stats
        m = self.metrics
        if not m:
            return
        col = {name: int(stats[:, i].sum()) for i, name in enumerate(STATS)}
        for kind in ("held", "identity", "absent"):
            m.moe_assignments.inc(col[kind], kind=kind)
        m.moe_identity.inc(col["identity"])
        m.moe_dropped.inc(col["dropped"])
        per_expert = self.moe_counts["decode"] + self.moe_counts["prefill"]
        m.moe_expert_peak.set(int(per_expert[:, len(STATS):].max()))
        for layer, row in enumerate(stats[:, len(STATS):]):
            for expert, n in zip(self.cfg.moe.held, row):
                if n:
                    m.moe_expert_tokens.inc(int(n), layer=str(layer), expert=str(expert))
        if phase == "decode":
            m.moe_decode_touched.inc(col["touched"])
            m.moe_decode_layer_steps.inc(col["active"])
            if self.moe_expert_kernel:
                m.moe_kernel_layer_steps.inc(col["active"])

    def _layer_steps_fold(self, steps: int) -> None:
        """Count one consumed decode dispatch of ``steps`` steps (a block
        counts its steps) against every latent attention layer, and every
        linear-attention layer."""
        m = self.metrics
        if not m:
            return
        if self.cfg.mla is not None:
            layer_steps = steps * len(self._layer_names)
            m.mla_decode_layer_steps.inc(layer_steps)
            if self.mla_latent_kernel:
                m.mla_kernel_layer_steps.inc(layer_steps)
        if self._linear_layers:
            layer_steps = steps * self._linear_layers
            m.kda_decode_layer_steps.inc(layer_steps)
            if self.kda_kernel:
                m.kda_kernel_layer_steps.inc(layer_steps)

    def kda_state(self) -> Optional[dict]:
        """The ``kda`` block of ``GET /debug/profile``: the linear-attention
        layers, whether their decode steps run the recurrence kernel, and
        the slot state's bytes; None for a model without them."""
        if not self._linear_layers:
            return None
        return {
            "layers": self._linear_layers, "kernel": self.kda_kernel,
            "slot_state_bytes": self.slot_state_bytes, "cache_bytes_per_token": self.cache_bytes_per_token,
        }

    def moe_state(self) -> Optional[dict]:
        """The ``moe`` block of ``GET /debug/profile``: the same sums as
        the tpu_engine_moe_* counters, by phase; None for a model without
        expert layers."""
        if self._moe_shape is None:
            return None
        out = {
            "held_experts": list(self.cfg.moe.held), "cache_bytes_per_token": self.cache_bytes_per_token,
            "cache_pad_bytes_per_token": self.cache_pad_bytes_per_token,
            "expert_kernel": self.moe_expert_kernel,
        }
        if self.cfg.mla is not None:
            # A latent row as the model defines it and as the pool stores it.
            out["latent_row"] = {"width": self.cfg.mla.row_width, "stored": self.cfg.mla.stored_width}
            out["latent_kernel"] = self.mla_latent_kernel
        for phase, counts in self.moe_counts.items():
            out[phase] = {
                **{name: int(counts[:, i].sum()) for i, name in enumerate(STATS)},
                "expert_tokens": counts[:, len(STATS):].tolist(),
            }
        return out

    def _record_hit(self) -> None:
        self.overlap_hits += 1
        if self.metrics:
            self.metrics.overlap_hits.inc()

    def _block_room(self, active: list[int]) -> int:
        """Smallest remaining token budget over the active slots — the
        bound on how many tokens any dispatch chain may run ahead."""
        return min(
            self.slots[s].max_new_tokens - len(self.slots[s].tokens)
            for s in active
        )

    def _block_step(
        self, active: list[int], finished: list[Request], T: int
    ) -> list[Request]:
        """Advance every active slot up to T tokens in ONE dispatch (the
        pure-decode fast path of step()).  A slot that hits EOS/max_new
        mid-block wastes its tail iterations (their K/V writes land past
        the row's final length and are masked forever after the rewind —
        the speculative round's exact discipline); everything the host
        consumes is identical to T single steps.  With overlap on, the
        NEXT block is dispatched before this one's readback (gated on
        room >= 2T so the overlapped block cannot overrun any slot's
        budget) — same state machine as the single-step pipeline."""
        with self.profiler.phase("dispatch"):
            overlap = (
                self._overlap_allowed() and self._block_room(active) >= 2 * T
            )
            rec = self._take_inflight(T)
            if rec is None:
                # Cold (or just-invalidated) pipeline: the frontier ensure
                # covers this block's writes — and the overlapped block's
                # too (lookahead 2T-1) when one will follow.
                active = self._ensure_frontier(
                    active, 2 * T - 1 if overlap else T - 1
                )
                if not active:
                    self._update_gauges()
                    return finished
                rec = self._dispatch_decode(active, T)
                if overlap:
                    self._inflight = self._dispatch_decode(active, T)
            else:
                self._record_hit()
                if overlap:
                    active = self._ensure_frontier(active, 2 * T - 1)
                    # An eviction inside the ensure dirtied the state: then
                    # this step consumes what it has and re-primes next call.
                    if active and self._dev is rec["dev"]:
                        self._inflight = self._dispatch_decode(active, T)
        return self._consume_block(rec, finished)

    def _consume_block(
        self, rec: dict, finished: list[Request]
    ) -> list[Request]:
        """Host half of one decode block: sync the packed readback, then
        per-slot consumption — under overlap this work executes while
        the next block computes on device (the host_gap phase)."""
        T = rec["T"]
        with self.profiler.phase("readback"):
            toks, lps = self._unpack(rec)
        with self.profiler.phase(
            "host_gap" if self._inflight is not None else "sample"
        ):
            self._moe_fold("decode", rec.get("moe_stats"))
            self._layer_steps_fold(rec["T"])
            now = time.monotonic()
            emitted_total = 0
            for s, req in zip(rec["active"], rec["reqs"]):
                if self.slots[s] is not req or not self._slot_ready[s]:
                    continue  # evicted between dispatch and sync
                consumed = 0
                for j in range(T):
                    tok = int(toks[s, j])
                    # Logprob BEFORE token: a streaming handler thread that
                    # snapshots between the two appends must never see a
                    # token whose logprob is missing.
                    if req.logprobs:
                        req.token_logprobs.append(float(lps[s, j]))
                    req.tokens.append(tok)
                    self._slot_last[s] = tok
                    consumed += 1
                    emitted_total += 1
                    if (
                        len(req.tokens) >= req.max_new_tokens
                        or (self.eos_id is not None and tok == self.eos_id)
                        or self._hit_stop(req)
                    ):
                        break
                self._slot_len[s] += consumed
                self._observe_itl(s, consumed, now)
                self._maybe_finish(s)
                if req.done:
                    finished.append(req)
                else:
                    self._extend_frontier(s)
                    if self.cfg.attention_window is not None:
                        self._reclaim_windowed(s)
            # The block left every row's device length at L+T (at L+2T with
            # an overlapped block in flight).  When every active slot
            # consumed all T tokens that IS the host truth (the in-flight
            # block accounts for its own +T when it is consumed); a
            # mid-block finish tore its slot down (_clear_slot -> state
            # dirty), and only then do device lengths disagree — the
            # in-flight discard re-aligns them, or the direct vector write
            # below does when nothing was in flight.
            if self._dev is None:
                if self._inflight is not None:
                    self._drop_stale_inflight("slot_teardown")
                else:
                    self._realign_seq_lens()
            self._step_tokens += emitted_total
            if self.metrics:
                self.metrics.steps.inc()
                self.metrics.tokens.inc(emitted_total)
            self._update_gauges()
        return finished

    def step(self) -> list[Request]:
        """Admit what fits, advance every active slot one token; returns
        every request that finished this step (including ones done at
        admission — EOS/max_new on the prefill token)."""
        self.profiler.begin_step()
        self._step_tokens = 0
        hits0, discards0 = self.overlap_hits, self.overlap_discards
        kv_hits0 = self.kv_retained_hits + self.kv_host_hits
        kv_restores0 = self.kv_restores
        wd = self.watchdog
        if wd is not None:
            wd.step_started()
        try:
            if self.metrics:
                with self.metrics.step_seconds.time():
                    return self._step_inner()
            return self._step_inner()
        finally:
            with self._lock:
                active = sum(1 for s in self.slots if s is not None)
                queued = len(self.queue)
                allocatable = self.paged.num_pages - 1
                util = (
                    1.0 - len(self.free_pages) / allocatable
                    if allocatable
                    else 0.0
                )
            wall = self.profiler.finish_step(
                active_slots=active,
                max_slots=self.max_slots,
                queued=queued,
                kv_page_utilization=util,
                tokens=self._step_tokens,
                overlap_hits=self.overlap_hits - hits0,
                overlap_discards=self.overlap_discards - discards0,
                kvcache_hits=(
                    self.kv_retained_hits + self.kv_host_hits - kv_hits0
                ),
                kvcache_restores=self.kv_restores - kv_restores0,
            )
            if wd is not None:
                wd.step_finished(wall)

    def _step_inner(self) -> list[Request]:
        with self.profiler.phase("schedule"):
            # Overload sweeps run BEFORE admission: an expired queued request
            # must shed (without ever touching pages) rather than admit, and
            # an infeasible slot must be marked so the cancel sweep below
            # frees it for the queue head.
            finished = self._overload_sweep() if self.overload is not None else []
            finished += self._admit()
            # Cancelled slots tear down BEFORE the dispatch (no farewell
            # token).  Only ready slots: a cancelled request mid-prefill
            # keeps its job's slot/pages intact until activation, whose own
            # _maybe_finish call then finishes it (this sweep catches
            # requests cancelled after they were already live).
            for s in range(self.max_slots):
                req = self.slots[s]
                if req is not None and req.cancelled and self._slot_ready[s]:
                    self._maybe_finish(s)
                    finished.append(req)
        with self.profiler.phase("prefill"):
            # Advance every in-flight prefill job by ONE chunk (an unchunked
            # job completes right here, in the same step() it was admitted):
            # chunking bounds how long active slots stall per step while a
            # long prompt streams in.
            for job in list(self._pending):
                if self._advance_prefill(job):
                    self._pending.remove(job)
                    finished.extend(self._activate(job))
        active = [
            s
            for s in range(self.max_slots)
            if self.slots[s] is not None and self._slot_ready[s]
        ]
        if not active:
            self._update_gauges()
            return finished
        if self._spec_gamma:
            return self._spec_step(active, finished)
        if (
            self._decode_block > 1
            and not self._pending  # no prompt mid-stream: keep chunking
            # Queued work argues for fine-grained steps ONLY while the
            # head could actually admit: a SATURATED engine (every slot
            # occupied — the steady operating point of a loaded server)
            # or a PAGE-BLOCKED head (this step's _admit broke on the
            # pool; only a finish or reclamation frees pages) cannot
            # admit until something releases, so it keeps blocking — a
            # mid-block finish truncates that slot's tail and the next
            # step() admits from the queue.  Otherwise stay fine-grained
            # so the queue head lands immediately.
            and (
                not self.queue
                or all(s is not None for s in self.slots)
                or self._admit_page_blocked
            )
        ):
            # Largest power-of-two block that no active slot's remaining
            # budget truncates (so no slot can overrun max_new mid-block).
            room = min(
                self.slots[s].max_new_tokens - len(self.slots[s].tokens)
                for s in active
            )
            T = min(self._decode_block, 1 << max(0, room.bit_length() - 1))
            if T > 1:
                return self._block_step(active, finished, T)
        with self.profiler.phase("dispatch"):
            overlap = self._overlap_allowed()
            rec = self._take_inflight(1)
            if rec is None:
                # Cold (or just-invalidated) pipeline: dispatch this step,
                # then prime the overlap from its fed-forward state.  The
                # next write (position len) must be addressable — and the
                # overlapped write (len+1) too when one will follow, hence
                # the one-token frontier lookahead; _block_step/_spec_step
                # run their own ensure with their larger lookaheads.
                if self._optimistic or overlap:
                    active = self._ensure_frontier(active, 1 if overlap else 0)
                    if not active:
                        self._update_gauges()
                        return finished
                rec = self._dispatch_decode(active)
                if overlap:
                    self._inflight = self._dispatch_decode(active)
            else:
                self._record_hit()
                if overlap:
                    # Keep one step in flight: ensure the NEXT write is
                    # addressable, then dispatch before the (blocking)
                    # readback of the consumed step.  An eviction inside the
                    # ensure dirtied the state — then this step consumes
                    # what it has and re-primes next call.
                    active = self._ensure_frontier(active, 1)
                    if active and self._dev is rec["dev"]:
                        self._inflight = self._dispatch_decode(active)
        return self._consume_step(rec, finished)

    def _consume_step(
        self, rec: dict, finished: list[Request]
    ) -> list[Request]:
        """Host half of one single-token step: sync the packed readback,
        then per-slot consumption (EOS/stop checks, frontier extension,
        reclamation, metrics) — under overlap this host work executes
        while the next step computes on device (the host_gap phase)."""
        with self.profiler.phase("readback"):
            toks, lps = self._unpack(rec)
        with self.profiler.phase(
            "host_gap" if self._inflight is not None else "sample"
        ):
            self._moe_fold("decode", rec.get("moe_stats"))
            self._layer_steps_fold(rec["T"])
            now = time.monotonic()
            consumed = 0
            for s, req in zip(rec["active"], rec["reqs"]):
                if self.slots[s] is not req or not self._slot_ready[s]:
                    continue  # evicted between dispatch and sync
                tok = int(toks[s])
                # Logprob BEFORE token (see _consume_block note).
                if req.logprobs:
                    req.token_logprobs.append(float(lps[s]))
                req.tokens.append(tok)
                self._slot_last[s] = tok
                self._slot_len[s] += 1
                consumed += 1
                self._observe_itl(s, 1, now)
                self._maybe_finish(s)
                if req.done:
                    finished.append(req)
                else:
                    self._extend_frontier(s)
                    if self.cfg.attention_window is not None:
                        self._reclaim_windowed(s)
            if self._dev is None:
                # A finish/cancel tore a slot down mid-consume: whatever is
                # still in flight was dispatched from pre-teardown state.
                self._drop_stale_inflight("slot_teardown")
            self._step_tokens += consumed
            if self.metrics:
                self.metrics.steps.inc()
                self.metrics.tokens.inc(consumed)
            self._update_gauges()
        return finished

    def _observe_itl(self, slot: int, consumed: int, now: float) -> None:
        """Observe inter-token latency for ``consumed`` tokens that landed
        at ``now`` on this slot.  Multi-token dispatches (decode blocks,
        speculative rounds) emit several tokens in one host round-trip:
        each observes the amortized gap dt/consumed, so the histogram sum
        stays wall-accurate and per-token quantiles stay meaningful."""
        last = self._slot_emit_t[slot]
        self._slot_emit_t[slot] = now
        if consumed <= 0 or last <= 0.0:
            return
        per = (now - last) / consumed
        req = self.slots[slot]
        if req is not None and per > req.itl_peak_s:
            # Per-request peak gap: the SLO plane's per-request ITL p99
            # stand-in (engine_types.Request.itl_peak_s).
            req.itl_peak_s = per
        if self.overload is not None:
            # The feasibility predicate's input: measured per-token
            # latency decides whether a deadline can still be met.
            self.overload.observe_itl(per)
        if not self.metrics:
            return
        for _ in range(consumed):
            self.metrics.itl_seconds.observe(per)

    def _update_gauges(self) -> None:
        if not self.metrics:
            return
        with self._lock:
            self.metrics.active_slots.set(
                sum(1 for s in self.slots if s is not None)
            )
            self.metrics.queued.set(len(self.queue))
            self.metrics.free_pages.set(len(self.free_pages))
            self.metrics.shared_pages.set(
                sum(1 for c in self._page_refs.values() if c > 1)
            )
            allocatable = self.paged.num_pages - 1  # page 0 is scratch
            self.metrics.page_utilization.set(
                1.0 - len(self.free_pages) / allocatable if allocatable else 0.0
            )
            self.metrics.kvcache_retained_pages.set(len(self._kv_retained))
            self.metrics.kvcache_host_bytes.set(self._kv_arena.bytes)

    def debug_state(self) -> dict:
        """JSON-safe engine snapshot for the /debug/state endpoint: what
        an operator needs to see DURING an incident — slot occupancy,
        queue depth, pool pressure, speculation counters — without
        attaching a debugger to the serving loop.  Token CONTENT is
        deliberately excluded (prompts are tenant data; lengths are not).
        Thread-safe: reads the cross-thread state under the engine lock
        (host lists owned by the step thread are read racily but are
        plain scalars/lists — a torn read shows one step's drift)."""
        with self._lock:
            slots = []
            for s in range(self.max_slots):
                req = self.slots[s]
                if req is None:
                    slots.append(None)
                    continue
                slots.append(
                    {
                        "rid": req.rid,
                        "trace_id": req.trace_id,
                        "prompt_tokens": len(req.prompt),
                        "generated": len(req.tokens),
                        "max_new_tokens": req.max_new_tokens,
                        "ready": self._slot_ready[s],
                        "pages": len(self._slot_pages[s]),
                        "cancelled": req.cancelled,
                    }
                )
            allocatable = self.paged.num_pages - 1
            return {
                # The backend this replica serves from, as JAX reports it.
                **self._device_facts,
                "slots": slots,
                "queue_depth": len(self.queue),
                "pending_prefills": len(self._pending),
                "free_pages": len(self.free_pages),
                "allocatable_pages": allocatable,
                "page_utilization": round(
                    1.0 - len(self.free_pages) / allocatable, 4
                )
                if allocatable
                else 0.0,
                "shared_pages": sum(
                    1 for c in self._page_refs.values() if c > 1
                ),
                "preemptions": self.preemptions,
                "overlap": {
                    "steps": self._overlap_steps,
                    "in_flight": self._inflight is not None,
                    "hits": self.overlap_hits,
                    "discards": self.overlap_discards,
                },
                "tp": {
                    "size": self.tp_size,
                    "axis": self._tp_axis if self.mesh is not None else None,
                    "mesh": dict(self.mesh.shape)
                    if self.mesh is not None
                    else None,
                    "devices": [str(d) for d in self.mesh.devices.flat]
                    if self.mesh is not None
                    else None,
                },
                "spec": {
                    "gamma": self._spec_gamma,
                    "proposed": self.spec_proposed,
                    "accepted": self.spec_accepted,
                },
                "overload": (
                    self.overload.snapshot()
                    if self.overload is not None
                    else {"enabled": False}
                ),
                "slo": (
                    self.slo.snapshot()
                    if self.slo is not None
                    else {"enabled": False}
                ),
                "kvcache": self.kvcache_state(),
                "disagg": self.handoff_state(),
                "config": {
                    "role": self.role,
                    "max_slots": self.max_slots,
                    "page_size": self.paged.page_size,
                    "num_pages": self.paged.num_pages,
                    "max_pages_per_seq": self.paged.max_pages_per_seq,
                    "kernel": self.kernel_on,
                    "kernel_splits": self.paged.kernel_num_splits,
                    "decode_block": self._decode_block,
                    "admission": "optimistic" if self._optimistic else "reserve",
                    "prefix_sharing": self.prefix_sharing,
                },
            }

    def overload_state(self) -> dict:
        """JSON-safe overload-controller snapshot for GET
        /debug/admission (``{"enabled": False}`` when the engine runs
        without a controller)."""
        with self._lock:
            if self.overload is None:
                return {"enabled": False}
            return self.overload.snapshot()

    def slo_state(self) -> dict:
        """JSON-safe SLO-plane snapshot for GET /debug/slo: objectives,
        window counts, burn rates, budget remaining, active alerts
        (``{"enabled": False}`` when the plane is off)."""
        with self._lock:
            if self.slo is None:
                return {"enabled": False}
            snap = self.slo.snapshot()
            snap["enabled"] = True
            return snap

    def usage_state(self) -> dict:
        """JSON-safe per-tenant usage snapshot for GET /debug/usage
        (``{"enabled": False}`` when the SLO plane is off)."""
        with self._lock:
            if self.usage is None:
                return {"enabled": False}
            snap = self.usage.snapshot()
            snap["enabled"] = True
            return snap

    def run(self, requests: list[tuple[list[int], int]], **submit_kw) -> list[Request]:
        """Submit all (``submit_kw`` — temperature/top_k/top_p — applies to
        every request), step until drained, return in submission order."""
        subs = [self.submit(p, n, **submit_kw) for p, n in requests]
        guard = 0
        while not all(r.done for r in subs):
            self.step()
            guard += 1
            if guard > 100_000:
                raise RuntimeError("engine failed to drain")
        return subs


def main(argv: Optional[list[str]] = None) -> None:
    """In-pod serving demo/benchmark (≙ the per-family benchmark pods in
    deploy/): synthetic weights + synthetic request stream through the
    continuous-batching engine; prints one JSON summary line.

    ``k8s-pod-serve-gpt.yaml`` runs this against allocated chips; the same
    command works on any backend (tiny CPU smoke by default).
    """
    import argparse
    import json
    import sys
    import time

    from ..utils.platform import enable_compilation_cache
    from ..utils.platform import positive_int as _positive_int

    enable_compilation_cache(log=lambda m: print(m, file=sys.stderr))

    p = argparse.ArgumentParser(prog="tpu-serving-engine")
    p.add_argument("--hidden", type=_positive_int, default=512)
    p.add_argument("--layers", type=_positive_int, default=4)
    p.add_argument("--heads", type=_positive_int, default=8)
    p.add_argument("--kv-heads", type=_positive_int, default=4)
    p.add_argument("--vocab", type=_positive_int, default=32000)
    p.add_argument("--quant", choices=["w8", "w8a8"], default=None)
    p.add_argument(
        "--quant-kv",
        action="store_true",
        help="int8 paged KV pools (halved cache bandwidth; gather path)",
    )
    p.add_argument("--page-size", type=_positive_int, default=16)
    p.add_argument("--num-pages", type=_positive_int, default=128)
    p.add_argument("--max-pages-per-seq", type=_positive_int, default=16)
    p.add_argument("--slots", type=_positive_int, default=4)
    p.add_argument("--requests", type=_positive_int, default=8)
    p.add_argument("--prompt-len", type=_positive_int, default=32)
    p.add_argument("--max-new", type=_positive_int, default=32)
    p.add_argument(
        "--use-kernel",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="decode through the split-K flash-decode paged-attention "
        "kernel instead of the gather path (ops/paged_attention.py; "
        "fused int8 dequant, per-generation split tables in "
        "ops/tuning.py); default auto — gather everywhere: the kernel "
        "lowers and agrees with gather on the v5e, its speed is not "
        "measured (docs/kernels.md)",
    )
    p.add_argument(
        "--kernel-splits",
        type=_positive_int,
        default=None,
        help="pin the paged kernel's split-K degree (default: the "
        "per-generation tuning table, ops/tuning.py — 1 on CPU smoke "
        "and short contexts)",
    )
    p.add_argument(
        "--temperature",
        type=float,
        default=0.0,
        help="sample every request at this temperature (0 = greedy)",
    )
    p.add_argument(
        "--top-k", type=_positive_int, default=None,
        help="restrict sampling to the k highest logits per step",
    )
    p.add_argument(
        "--top-p", type=float, default=None,
        help="restrict sampling to the smallest nucleus with mass >= p",
    )
    p.add_argument(
        "--spec-gamma",
        type=int,
        default=0,
        help="speculative decoding: gamma int8 self-draft proposals per "
        "verify pass (shared-pool; greedy slots emit exactly the greedy "
        "decode, sampled slots marginally exact filtered samples). "
        "Incompatible with --quant.",
    )
    p.add_argument(
        "--prefill-chunk",
        type=_pow2_int,
        default=None,
        help="stream prompts into the prefill in chunks of this many "
        "tokens (power of two), bounding how long active slots stall "
        "per step during a long admission",
    )
    p.add_argument(
        "--decode-block",
        type=_pow2_int,
        default=1,
        help="in pure decode (no admission work), advance every slot up "
        "to this many tokens per dispatch via one scanned program "
        "(power of two) — amortizes the per-step host round-trip; under "
        "saturation a finishing request's slot is refilled at the next "
        "step boundary, so blocks add up to block-size steps of "
        "first-token wait; incompatible with --spec-gamma",
    )
    p.add_argument(
        "--overlap-steps",
        type=int,
        choices=[0, 1],
        default=1,
        help="decode dispatches kept in flight ahead of host consumption "
        "(1: dispatch step N+1 before consuming step N's readback, hiding "
        "per-token host work behind device compute; invalidating events — "
        "admission, finish, cancel, preemption — discard the in-flight "
        "step at the cost of one wasted lane; 0: strictly synchronous "
        "loop; speculative engines always run synchronously)",
    )
    p.add_argument(
        "--admission",
        choices=["reserve", "optimistic"],
        default="reserve",
        help="reserve: allocate each request's worst-case page chain at "
        "admission (no preemption ever); optimistic: allocate prompt "
        "pages only and grow on demand, preempting the newest slot for "
        "recompute-resume when the pool runs dry — higher concurrency "
        "when generations finish early",
    )
    p.add_argument(
        "--overload",
        type=int,
        choices=[0, 1],
        default=1,
        help="overload control (models/engine_overload.py): priority + "
        "deadline-aware admission with per-tenant fair sharing, expiry "
        "sweeping, and an AIMD concurrency limiter driven by measured "
        "queue wait (default on; 0 restores the plain FIFO queue — "
        "streams are bit-identical either way for deadline-free "
        "uniform-priority traffic)",
    )
    p.add_argument(
        "--overload-target-wait",
        type=float,
        default=0.5,
        help="AIMD setpoint: the queue wait (seconds) the overload "
        "limiter steers admitted concurrency toward",
    )
    p.add_argument(
        "--overload-max-queue",
        type=int,
        default=512,
        help="hard queue cap: submits past this depth shed immediately "
        "with 503 + Retry-After regardless of priority",
    )
    p.add_argument(
        "--slo",
        type=int,
        choices=[0, 1],
        default=1,
        help="SLO plane (utils/slo.py): per-request SLI verdicts (TTFT, "
        "per-request ITL p99, availability) into sliding-window error "
        "budgets with burn-rate alerting, plus per-tenant usage meters "
        "(default on; 0 disables all accounting — zero per-request cost)",
    )
    p.add_argument(
        "--slo-ttft-target",
        type=float,
        default=2.0,
        help="TTFT objective threshold (seconds): a request whose first "
        "token lands later counts against the ttft error budget",
    )
    p.add_argument(
        "--slo-itl-target",
        type=float,
        default=0.25,
        help="per-request ITL p99 objective threshold (seconds): a "
        "request whose worst inter-token gap exceeds this counts "
        "against the itl_p99 error budget",
    )
    p.add_argument(
        "--kv-retain",
        type=int,
        choices=[0, 1],
        default=1,
        help="KV cache tier 1: keep dead-but-valid prefix pages on an "
        "LRU instead of freeing them, so a repeated prompt prefix (or a "
        "preemption resume) restores from the page pool instead of "
        "recomputing; retained pages are reclaimed lazily whenever the "
        "free pool alone cannot satisfy a request (default on)",
    )
    p.add_argument(
        "--kv-host-cache-mb",
        type=float,
        default=64,
        help="KV cache tier 2: byte budget (MiB) of the host-RAM arena "
        "that reclaimed retained pages and preemption snapshots spill "
        "into; matched entries restore device-side with sliced page "
        "writes — no recompute, no new compiled shapes (0 disables the "
        "host tier; default 64)",
    )
    p.add_argument(
        "--tp",
        type=_positive_int,
        default=1,
        help="tensor-parallel degree: shard params (Megatron path rules) "
        "and KV pools (kv-heads axis) over a mesh built from the chips "
        "the plugin allocated — TPU_VISIBLE_CHIPS in physical ICI snake "
        "order (parallel/mesh.mesh_from_allocation); must equal the "
        "granted chip count on-cluster, and kv-heads must divide by it; "
        "off-cluster falls back to the first N jax.devices(); 1 = "
        "single-chip (default)",
    )
    args = p.parse_args(argv)
    if args.spec_gamma and args.quant:
        raise SystemExit(
            "--spec-gamma uses the int8 SELF-draft against the bf16 "
            "target; an already-quantized target (--quant) leaves nothing "
            "to verify against — drop one of the flags"
        )

    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_heads=args.heads,
        intermediate_size=args.hidden * 3,
        max_seq=args.page_size * args.max_pages_per_seq,
        num_kv_heads=args.kv_heads,
    )
    rng = jax.random.PRNGKey(0)
    params = TransformerLM(cfg).init(rng, jnp.zeros((1, 2), jnp.int32))["params"]
    if args.quant:
        from ..ops.quant import quantize_lm_params

        params = quantize_lm_params(params)
        cfg = dataclasses.replace(cfg, quant=args.quant)
    if args.quant_kv:
        cfg = dataclasses.replace(cfg, quant_kv=True)
    paged = PagedConfig(
        args.page_size,
        args.num_pages,
        args.max_pages_per_seq,
        use_kernel=args.use_kernel,
        kernel_num_splits=args.kernel_splits,
    )
    spec_kw = {}
    if args.spec_gamma:
        from ..ops.quant import quantize_lm_params

        spec_kw = dict(
            spec_gamma=args.spec_gamma,
            draft_params=quantize_lm_params(params),
        )
    from ..utils.metrics import MetricsRegistry

    mesh = None
    if args.tp > 1:
        from ..parallel.mesh import mesh_from_allocation

        mesh = mesh_from_allocation(args.tp)
        print(
            f"tensor parallel: tp={args.tp} over "
            f"{[str(d) for d in mesh.devices.flat]}",
            file=sys.stderr,
        )
    registry = MetricsRegistry()
    overload_cfg = None
    if args.overload:
        from .engine_overload import OverloadConfig

        overload_cfg = OverloadConfig(
            target_queue_wait_s=args.overload_target_wait,
            max_queue=args.overload_max_queue,
        )
    slo_cfg = None
    if args.slo:
        slo_cfg = {
            "ttft_target_s": args.slo_ttft_target,
            "itl_p99_target_s": args.slo_itl_target,
        }
    eng = ServingEngine(
        cfg, params, paged, max_slots=args.slots,
        metrics=EngineMetrics(registry),
        prefill_chunk=args.prefill_chunk, decode_block=args.decode_block,
        overlap_steps=args.overlap_steps,
        admission=args.admission,
        overload=overload_cfg,
        slo=slo_cfg,
        kv_retain=bool(args.kv_retain),
        kv_host_cache_mb=args.kv_host_cache_mb,
        mesh=mesh,
        **spec_kw,
    )
    # Under --tp the engine holds its own sharded copy (http_server.py
    # main() has the measurement).
    del params, spec_kw
    sample_kw = dict(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p
    )

    # Half the stream shares a system-prompt prefix (exercises page sharing).
    common = list(range(1, args.prompt_len // 2 + 1))
    jobs = []
    for i in range(args.requests):
        tail = [(37 * i + j) % args.vocab for j in range(args.prompt_len // 2)]
        prompt = (common + tail) if i % 2 == 0 else [(11 * i + j) % args.vocab for j in range(args.prompt_len)]
        jobs.append((prompt, args.max_new))

    # Warmup: compile the fixed-slot step and EVERY distinct prompt-length
    # prefill OUTSIDE the timed region (max_new=2 forces one decode step),
    # so the JSON line reports steady-state serving throughput, not XLA
    # compilation — the same honesty rule every bench in this repo follows.
    warm_lens: dict[int, list[int]] = {}
    for prompt, _ in jobs:
        warm_lens.setdefault(len(prompt), prompt)
    eng.run([(prompt, 2) for prompt in warm_lens.values()], **sample_kw)
    # Warmup rounds ran real speculative traffic; the reported acceptance
    # must cover the timed region only (same warmup-exclusion rule as the
    # throughput number).
    eng.spec_proposed = eng.spec_accepted = 0
    # Latency percentiles come back from the SAME registry histograms
    # operators scrape — snapshotted here so warmup (compile-dominated
    # TTFTs of seconds) is subtracted from the reported quantiles.
    ttft_h, itl_h = eng.metrics.ttft_seconds, eng.metrics.itl_seconds
    ttft_snap, itl_snap = ttft_h.snapshot(), itl_h.snapshot()

    def _ms(value):
        return None if value is None else round(value * 1e3, 3)

    t0 = time.time()
    done = eng.run(jobs, **sample_kw)
    dt = time.time() - t0
    tokens = sum(len(r.tokens) for r in done)
    print(
        json.dumps(
            {
                "metric": "engine_decode_tokens_per_sec",
                "value": round(tokens / dt, 2),
                "unit": "tokens/sec",
                "requests": len(done),
                "slots": args.slots,
                "tp": args.tp,
                "quant": args.quant,
                "kernel": paged.kernel_enabled(cfg.quant_kv),
                "sampler": "greedy"
                if args.temperature <= 0
                else f"temperature={args.temperature},top_k={args.top_k},"
                f"top_p={args.top_p}",
                "spec_gamma": args.spec_gamma,
                "spec_acceptance": round(
                    eng.spec_accepted / max(eng.spec_proposed, 1), 3
                )
                if args.spec_gamma
                else None,
                "tokens": tokens,
                "wall_s": round(dt, 2),
                "overlap_steps": args.overlap_steps,
                "overlap_hits": eng.overlap_hits,
                "overlap_discards": eng.overlap_discards,
                "kv_retain": bool(args.kv_retain),
                "kv_retained_hits": eng.kv_retained_hits,
                "kv_host_hits": eng.kv_host_hits,
                "ttft_p50_ms": _ms(ttft_h.quantile(0.5, since=ttft_snap)),
                "ttft_p99_ms": _ms(ttft_h.quantile(0.99, since=ttft_snap)),
                "itl_p50_ms": _ms(itl_h.quantile(0.5, since=itl_snap)),
                "itl_p99_ms": _ms(itl_h.quantile(0.99, since=itl_snap)),
            }
        ),
        file=sys.stdout,
        flush=True,
    )


if __name__ == "__main__":
    main()
