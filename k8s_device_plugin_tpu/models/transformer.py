"""Decoder-only transformer LM — the long-context flagship workload.

The reference repo ships no model code at all (SURVEY.md §2.4: its "model" is
an external benchmark container, k8s-pod-example-gpu.yaml:10-19); this family
exists so the TPU plugin has a first-party long-context workload to allocate
chips to.  TPU-first choices:

- bfloat16 matmuls with float32 RMSNorm/softmax accumulation (MXU-friendly);
- causal attention through the fused Pallas flash kernel
  (ops/flash_attention.py) whenever the sequence tiles into 128-blocks,
  plain-XLA oracle otherwise — both share parameters, checkpoints are
  portable between paths;
- rotary position embeddings (no learned position table to shard);
- a `decode` mode with a KV cache carried in flax's ``cache`` collection so
  autoregressive generation is a `lax`-scannable fixed-shape step;
- parameter shapes laid out so Megatron-style tensor parallelism
  (parallel/tensor.py) can split heads/ffn over a ``tp`` mesh axis, and
  sequence parallelism (parallel/ring.py, parallel/ulysses.py) can split the
  sequence over ``sp``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash_attention import flash_attention, mha_reference
from ..ops.quant import Int8DenseGeneral, dequantize_kv, quantize_kv_pair
from .mla import LatentAttention, MlaConfig
from .moe import ExpertLayer, MoeConfig
from .ssm import Mamba2Mixer, MambaConfig

# Large-negative logit for top-k filtering: finite (softmax/categorical
# stay NaN-free even if every logit in a row were filtered) yet far below
# any real logit after temperature scaling.
NEG_LOGIT = -1e30


@dataclass(frozen=True)
class PagedConfig:
    """Paged-KV-cache geometry (vLLM-style, static-shape TPU variant).

    The decode cache becomes a shared page pool ``[num_pages, page_size,
    kv_heads, head_dim]`` plus a per-slot page table ``[batch,
    max_pages_per_seq]`` and length vector — sequences of different lengths
    share one physical pool, so HBM capacity is allocated by USE, not by
    worst-case ``max_seq`` per row (the continuous-batching memory model;
    models/engine.py schedules slots/pages host-side).
    """

    page_size: int = 16
    num_pages: int = 256
    max_pages_per_seq: int = 16
    # Read pages through the split-K flash-decode paged-attention kernel
    # (ops/paged_attention.py: scalar-prefetched page table, O(len) HBM
    # traffic, each row's page list partitioned across a split grid axis
    # with an exact online-softmax combine) instead of materializing the
    # gathered [max_len] view.  Sliding windows mask inside the kernel
    # (attention_window composes), and int8 KV pools (quant_kv) stream
    # as int8 with their scale pools riding along and dequantization
    # fused onto the score matrix — no bf16 copy ever lands in HBM.
    # On CPU the same split-K math runs as a vectorized XLA program
    # (the interpreter is a parity lane, not a serving path), which is
    # what moved the KERNELS smoke ledger from 0.06-0.12x of the gather
    # path to >=1x (benchmark.py --kernel).
    # None = auto: the GATHER path everywhere, still.  The builder
    # session of 2026-08-01 (before PR 1, record deleted in PR 21, not
    # re-measured) saw the OLD single-pass kernel lose to XLA's
    # gather+einsum at moderate contexts (0.82-0.91x standalone).  The
    # split-K rewrite lowers under Mosaic on the v5e and agrees with the
    # gather path for all three pool formats (chip run, PR 21), but its
    # speed is not measured, so auto stays gather until a cell compares
    # the two (ops/tuning.py, docs/kernels.md "Fallback & parity
    # contract", ROADMAP Speed 5).  Explicit True forces the kernel — on
    # a TPU that is the compiled Mosaic kernel, never the interpreter or
    # the XLA lane (all pool formats); explicit False forces gather.
    use_kernel: bool | None = None
    # Split-K degree override: None = the per-generation tuning table
    # (ops/tuning.py — degenerate 1-split on CPU and short contexts,
    # where the combine stage is skipped entirely).
    kernel_num_splits: Optional[int] = None

    def kernel_enabled(self, quant_kv: bool = False) -> bool:
        """Resolve the tri-state ``use_kernel`` at trace time (auto =
        gather until a chip cell measures the split-K kernel against it
        — the engine meters the resolution via tpu_engine_kernel_enabled
        and `kernel.fallback` flight events, models/engine.py)."""
        if self.use_kernel is None:
            return False
        return self.use_kernel

    @property
    def max_len(self) -> int:
        return self.page_size * self.max_pages_per_seq


@dataclass(frozen=True)
class Multipliers:
    """muP-style fixed scalars a published config multiplies activations
    by (all 1.0: nothing is scaled and nothing is traced for them)."""

    embedding: float = 1.0  # the embedded tokens
    lm_head: float = 1.0  # the logits
    attention_in: float = 1.0  # the normed hidden state into attention
    key: float = 1.0  # the key projection, before the rotation
    attention_out: float = 1.0  # attention's output into the residual
    ssm_in: float = 1.0  # the normed hidden state into the mixer
    ssm_out: float = 1.0  # the mixer's output into the residual
    mlp_gate: float = 1.0  # the gate projection, inside the activation
    mlp_down: float = 1.0  # the feed-forward's output into the residual


def _scaled(x, m: float):
    """``x * m``; ``x`` itself where the multiplier is 1 (no operation)."""
    return x if m == 1.0 else x * m


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    intermediate_size: int = 5632
    max_seq: int = 4096
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # Rematerialize each decoder block in the backward pass (jax.checkpoint):
    # trades recompute FLOPs for activation HBM — the standard long-context
    # memory lever alongside sequence parallelism.
    remat: bool = False
    # Grouped-query attention: number of kv heads (None = num_heads = MHA;
    # 1 = MQA).  Shrinks the decode KV cache by num_heads/num_kv_heads —
    # the HBM lever for long-context inference.
    num_kv_heads: Optional[int] = None
    # Sliding-window (Mistral-style) local attention: each token sees only
    # its `attention_window` most recent positions.  None = full causal.
    # The flash kernel skips out-of-band tiles (forward) and restricts the
    # chunked backward to each block's query band, so training compute
    # scales O(seq·window) instead of O(seq²).
    attention_window: Optional[int] = None
    # Post-training int8 quantization mode for every dense site (ops/quant.py):
    # None = bf16 (training), "w8" = int8 weights dequantized in-register
    # (the decode bandwidth mode), "w8a8" = dynamic activation quant +
    # int8 MXU matmuls (the prefill/batch throughput mode; 2x bf16 MXU rate
    # on v5e).  Params for a quantized config come from
    # ops.quant.quantize_lm_params on a trained bf16 tree — embeddings and
    # norms stay full-precision.
    quant: Optional[str] = None
    # int8 KV cache (decode only): cache slabs store int8 with per-token,
    # per-head scales — half the cache HBM bytes AND half the per-step
    # cache read traffic, the long-context decode lever (decode is
    # KV-bandwidth-bound once seq >> hidden).  Orthogonal to `quant`
    # (weights); either works alone, the serving config sets both.
    quant_kv: bool = False
    # LoRA fine-tuning (models/lora.py): rank-r adapters on every dense
    # site, base kernels frozen (`kernel` keeps its plain name/shape, so a
    # pretrained checkpoint loads as-is and adapters init as a no-op).
    # Train with make_lora_tx(inner_tx); merge_lora_params folds adapters
    # back for serving.  Mutually exclusive with `quant` (quantize AFTER
    # merging).
    lora_rank: Optional[int] = None
    lora_alpha: float = 16.0
    # Multi-LoRA serving (models/lora.py MultiLoRADense): number of stacked
    # adapters every dense site carries (0 = off).  Requires lora_rank; the
    # model then takes a per-row ``adapter_ids`` [batch] input (-1 = base
    # only) and the serving engine maps each request's adapter choice onto
    # its slot — many fine-tunes, one set of base weights, one jitted step.
    # Build the params with lora.stack_lora_adapters.
    lora_serve: int = 0
    # Paged KV cache for continuous-batching serving (models/engine.py):
    # decode reads/writes page-table-indirected pool slabs instead of one
    # dense [batch, max_seq] cache.  Single-token decode steps only — the
    # engine prefills through the dense path and grafts the rows into
    # pages.  Composes with quant_kv (int8 pools + scale pools; the r2
    # exclusion closed in r3 — tests/test_engine.py pins both paths).
    paged: Optional[PagedConfig] = None
    # Width of one attention head.  None = hidden_size // num_heads, what
    # every model here had; a published config may give another (20 heads
    # of 128 on a hidden size of 5120).  Resolved in __post_init__, so a
    # dataclasses.replace that changes hidden_size or num_heads passes
    # head_dim=None (or the new width) with them.
    head_dim: Optional[int] = None
    rms_norm_eps: float = 1e-6
    # Positions of a prefilled prompt whose logits are computed (a
    # published config's ``num_logits_to_keep``).  None = all of them, what
    # every model here did; 1 = only each row's last real position goes
    # through the head (the engine's chunk program selects the row first:
    # with a vocabulary of 261,120 the float32 logits of a 32 x 256 chunk
    # would be 8.5 GB nobody reads).
    logits_to_keep: Optional[int] = None
    multipliers: Multipliers = Multipliers()
    # A Mamba-2 mixer beside attention in every block (models/ssm.py): ONE
    # pre-norm feeds both, their scaled outputs are summed into the
    # residual.  Its recurrent state is per sequence, not per token: the
    # serving engine keeps it in per-slot leaves beside the paged pools.
    mixer: Optional[MambaConfig] = None
    # Latent attention in place of grouped-query attention (models/mla.py):
    # a token's cache row is one latent and one rotary key for all heads
    # (``pool_latent`` / ``cached_latent`` where K and V pools were);
    # ``num_kv_heads`` and ``head_dim`` are then unused.  Comes with
    # ``moe``: one block has both.
    mla: Optional[MlaConfig] = None
    # Shortcut-connected expert layers (models/moe.py, ``ShortcutBlock``):
    # the decoder is pairs of blocks, ``num_layers`` counts the blocks (one
    # attention and one cache-tree layer each, so an even number), and the
    # expert layer of a pair reads the first block's post-attention norm and
    # joins the residual at the END of the second: it runs beside a whole
    # attention and feed-forward.
    moe: Optional[MoeConfig] = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_heads)
        if self.logits_to_keep not in (None, 1):
            raise ValueError(f"logits_to_keep must be None or 1, got {self.logits_to_keep}")
        if (self.mla is None) != (self.moe is None):
            raise ValueError(
                "cfg.mla and cfg.moe come together: ShortcutBlock is the one block "
                "with latent attention and the one with an expert layer"
            )
        if self.moe is not None and self.num_layers % 2:
            raise ValueError(
                f"num_layers {self.num_layers} must be even with cfg.moe: it counts "
                "the blocks of shortcut-connected pairs"
            )
        if self.moe is not None and self.mixer is not None:
            raise ValueError("cfg.moe (ShortcutBlock) has no mixer beside its attention")

    @property
    def kv_heads(self) -> int:
        return self.num_heads if self.num_kv_heads is None else self.num_kv_heads

    @staticmethod
    def tiny() -> "GPTConfig":
        """Structural stand-in for CPU tests: every width divisible by small
        tp/ep axis sizes, sequence lengths kept off the flash path."""
        return GPTConfig(
            vocab_size=512,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            intermediate_size=128,
            max_seq=128,
            dtype=jnp.float32,
        )


class RMSNorm(nn.Module):
    """Root-mean-square norm, computed in float32 regardless of input dtype."""

    dtype: Any = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embeddings, float32. positions: [...,seq]."""
    freqs = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., seq, head_dim/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[2i], x[2i+1]); x: [batch, seq, heads, head_dim]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def dense_site(cfg: GPTConfig, features, *, axis=-1, dtype=None, name: str):
    """One constructor for every matmul-bearing projection in the model:
    flax Dense/DenseGeneral when ``cfg.quant`` is None, Int8DenseGeneral
    (same parameter tree shape, ``kernel`` -> ``kernel_q``/``kernel_scale``)
    otherwise — training and quantized serving share ALL model code."""
    dtype = cfg.dtype if dtype is None else dtype
    if cfg.quant is not None and cfg.lora_rank is not None:
        raise ValueError(
            "quant and lora_rank are mutually exclusive: train the adapters, "
            "merge_lora_params, then quantize the merged tree"
        )
    if cfg.lora_serve:
        if cfg.lora_rank is None:
            raise ValueError("lora_serve requires lora_rank")
        from .lora import MultiLoRADense

        return MultiLoRADense(
            features=features,
            rank=cfg.lora_rank,
            n_adapters=cfg.lora_serve,
            alpha=cfg.lora_alpha,
            axis=axis,
            dtype=dtype,
            name=name,
        )
    if cfg.lora_rank is not None:
        from .lora import LoRADense  # local: lora imports ops, not us

        return LoRADense(
            features=features,
            rank=cfg.lora_rank,
            alpha=cfg.lora_alpha,
            axis=axis,
            dtype=dtype,
            name=name,
        )
    if cfg.quant is None:
        # DenseGeneral(features=int, axis=-1) == Dense: same "kernel"
        # [in, out] param, same init, same dot — one constructor suffices.
        return nn.DenseGeneral(
            features=features, axis=axis, dtype=dtype, use_bias=False, name=name
        )
    return Int8DenseGeneral(
        features=features, axis=axis, mode=cfg.quant, dtype=dtype, name=name
    )


def _site_call(mod, x, cfg: GPTConfig, adapter_ids):
    """Apply a dense site built by :func:`dense_site`.  Multi-LoRA serving
    sites (``cfg.lora_serve``) additionally take the traced per-row adapter
    id vector; every other site kind has the plain one-argument call."""
    if cfg.lora_serve:
        return mod(x, adapter_ids)
    return mod(x)


def cached_group_attention(q, k, v, positions, window, num_heads):
    """Masked grouped-query attention against a cache view.

    q: [batch, q_len, num_heads, head_dim]; k/v: [batch, L, kv_heads,
    head_dim] (a dense cache or a gathered page view — the one attention
    both decode cache layouts share).  Each query at absolute position
    ``positions[b, i]`` sees cache slots ``<= position`` (and within the
    sliding window when set); the kv heads are read once per group via a
    grouped einsum — never expanded.
    """
    batch, q_len, _, head_dim = q.shape
    length, kv_heads = k.shape[1], k.shape[2]
    group = num_heads // kv_heads
    qg = q.reshape(batch, q_len, kv_heads, group, head_dim)
    key_pos = jnp.arange(length)[None, None, None, None, :]
    q_pos = positions[:, None, None, :, None]  # [b, 1, 1, q_len, 1]
    mask = key_pos <= q_pos
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - key_pos < window)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * (head_dim ** -0.5)
    s = jnp.where(mask, s, NEG_LOGIT)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(
        batch, q_len, num_heads, head_dim
    )


def tiled_causal_attention(qh, kh, vh, window):
    """Causal attention on [batch, heads, seq, head_dim]: the fused flash
    kernel when the sequence is 128-tileable, the plain-XLA oracle
    otherwise (same parameters either way) — the one dispatch rule the
    training and bulk-prefill paths share."""
    if qh.shape[2] % 128 == 0:
        return flash_attention(qh, kh, vh, causal=True, window=window)
    return mha_reference(qh, kh, vh, causal=True, window=window)


class CausalSelfAttention(nn.Module):
    """Causal MHA with RoPE; fused flash kernel on 128-tileable sequences.

    In ``decode`` mode a fixed-shape KV cache lives in the ``cache``
    collection (cached_key/cached_value/cache_index), so a single-token step
    has static shapes and is scannable under jit.
    """

    config: GPTConfig
    decode: bool = False
    # Optional override for the core attention computation, signature
    # ``(q, k, v, causal=..., sm_scale=...) -> out`` on [batch, heads, seq,
    # head_dim] — the hook parallel/sequence.py uses to swap in ring or
    # Ulysses sequence-parallel attention.  Ignored in decode mode.
    attention_fn: Optional[Any] = None
    # Decode-mode multi-token semantics.  "auto": a q_len > 1 step is a
    # bulk PREFILL into an empty cache (attends only within the provided
    # tokens — flash-tiled).  "cached": a q_len > 1 step is an APPEND that
    # attends against the whole cache with per-query position masks — the
    # contract speculative verification needs (γ+1 draft tokens scored in
    # one pass against a non-empty cache, models/speculative.py).
    append_mode: str = "auto"

    @nn.compact
    def __call__(self, hidden, positions, adapter_ids=None):
        cfg = self.config
        if self.append_mode not in ("auto", "cached"):
            # A typo here would silently pick the [q_len, max_seq] masked
            # path for bulk prefill — a large, erroneous memory/time blowup.
            raise ValueError(
                f"append_mode must be auto|cached, got {self.append_mode!r}"
            )
        if cfg.num_heads % cfg.kv_heads:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by kv_heads {cfg.kv_heads}"
            )
        if cfg.attention_window is not None and cfg.attention_window < 1:
            raise ValueError(
                f"attention_window must be >= 1, got {cfg.attention_window}"
            )
        group = cfg.num_heads // cfg.kv_heads
        proj = {
            name: _site_call(
                dense_site(cfg, (heads, cfg.head_dim), name=name),
                hidden,
                cfg,
                adapter_ids,
            )
            for name, heads in (
                ("query", cfg.num_heads),
                ("key", cfg.kv_heads),
                ("value", cfg.kv_heads),
            )
        }  # [batch, seq, (kv_)heads, head_dim]
        cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(proj["query"], cos, sin)
        k = apply_rope(_scaled(proj["key"], cfg.multipliers.key), cos, sin)
        v = proj["value"]

        if self.decode and cfg.paged is not None:
            # Paged cache: one shared pool, page-table indirection per slot
            # (PagedConfig).  Single-token decode steps, plus multi-token
            # appends for the speculative verify pass — the serving engine
            # (models/engine.py) prefills via the dense path and grafts
            # rows into pages, and it reserves page 0 as the idle-slot
            # scratch target so inactive rows never collide with live
            # pages.
            pg = cfg.paged
            batch, q_len = hidden.shape[:2]
            pool_shape = (pg.num_pages, pg.page_size, cfg.kv_heads, cfg.head_dim)
            if cfg.quant_kv:
                # int8 page pools + per-(slot, head) scale pools: the same
                # KV-bandwidth halving the dense cache gets, for paged
                # serving (long context is exactly where the pool is big).
                pk = self.variable("cache", "pool_key", jnp.zeros, pool_shape, jnp.int8)
                pv = self.variable("cache", "pool_value", jnp.zeros, pool_shape, jnp.int8)
                sshape = (pg.num_pages, pg.page_size, cfg.kv_heads)
                psk = self.variable(
                    "cache", "pool_key_scale", jnp.zeros, sshape, jnp.float32
                )
                psv = self.variable(
                    "cache", "pool_value_scale", jnp.zeros, sshape, jnp.float32
                )
                # ONE fused quantization pass per append: the K/V pair
                # stacks through a single amax/round/clip, and the scale
                # rows land in the scale pools alongside the page write —
                # nothing downstream (graft, kernel, gather) ever
                # re-derives a scale (ops/quant.py quantize_kv_pair;
                # bit-identical to two quantize_kv calls).
                k_store, v_store, ks, vs = quantize_kv_pair(k, v)
            else:
                pk = self.variable("cache", "pool_key", jnp.zeros, pool_shape, k.dtype)
                pv = self.variable("cache", "pool_value", jnp.zeros, pool_shape, v.dtype)
                k_store, v_store = k, v
            table = self.variable(
                "cache",
                "page_table",
                jnp.zeros,
                (batch, pg.max_pages_per_seq),
                jnp.int32,
            )
            lens = self.variable("cache", "seq_lens", jnp.zeros, (batch,), jnp.int32)
            cur = lens.value  # first written position per row
            if q_len == 1:
                row = jnp.arange(batch)
                page = table.value[row, cur // pg.page_size]
                off = cur % pg.page_size
                pk.value = pk.value.at[page, off].set(k_store[:, 0])
                pv.value = pv.value.at[page, off].set(v_store[:, 0])
                if cfg.quant_kv:
                    psk.value = psk.value.at[page, off].set(ks[:, 0])
                    psv.value = psv.value.at[page, off].set(vs[:, 0])
            else:
                # Multi-token paged append (the speculative verify pass):
                # scatter q_len consecutive positions per row through the
                # table in one update.  Rows at different lens may share
                # scratch page 0 (idle slots) — garbage there is masked.
                offs = cur[:, None] + jnp.arange(q_len)[None, :]  # [b, q]
                page = table.value[
                    jnp.arange(batch)[:, None], offs // pg.page_size
                ]
                pk.value = pk.value.at[page, offs % pg.page_size].set(k_store)
                pv.value = pv.value.at[page, offs % pg.page_size].set(v_store)
                if cfg.quant_kv:
                    psk.value = psk.value.at[page, offs % pg.page_size].set(ks)
                    psv.value = psv.value.at[page, offs % pg.page_size].set(vs)
            lens.value = cur + q_len
            # The kernel is single-token by design; multi-token appends
            # (the speculative verify pass) ride the gather path below —
            # its per-query masks handle in-block causality — so
            # use_kernel engines still spec.
            if pg.kernel_enabled(cfg.quant_kv) and q_len == 1:
                from ..ops.paged_attention import paged_attention

                # Pages stream straight from the pool via the scalar-
                # prefetched table; valid slots per row = position + 1
                # (this token's K/V were just written above).  A sliding
                # window masks inside the kernel (and skips wholly-dead
                # pages), mirroring the gather path's mask.  int8 pools
                # (quant_kv) stream as int8 — half the traffic — with
                # their scale pools riding along and dequantization fused
                # onto the score matrix.  The split degree comes from the
                # per-generation tuning table unless pinned on the config.
                attn = paged_attention(
                    q[:, 0],
                    pk.value,
                    pv.value,
                    table.value,
                    positions[:, 0] + 1,
                    window=cfg.attention_window,
                    scale_k=psk.value if cfg.quant_kv else None,
                    scale_v=psv.value if cfg.quant_kv else None,
                    num_splits=pg.kernel_num_splits,
                )[:, None]
            else:
                # Gather each row's pages into its logical [max_len] view
                # (a scope of its own: in a device trace the gathered copy
                # is otherwise only ``%copy.N`` under ``attn``).
                with jax.named_scope("paged_gather"):
                    kr = pk.value[table.value].reshape(
                        batch, pg.max_len, cfg.kv_heads, cfg.head_dim
                    )
                    vr = pv.value[table.value].reshape(
                        batch, pg.max_len, cfg.kv_heads, cfg.head_dim
                    )
                if cfg.quant_kv:
                    # int8 stays the HBM format; the dequant fuses into
                    # the gather/einsum reads (≙ the dense quant_kv path).
                    kr = dequantize_kv(
                        kr,
                        psk.value[table.value].reshape(
                            batch, pg.max_len, cfg.kv_heads
                        ),
                        cfg.dtype,
                    )
                    vr = dequantize_kv(
                        vr,
                        psv.value[table.value].reshape(
                            batch, pg.max_len, cfg.kv_heads
                        ),
                        cfg.dtype,
                    )
                attn = cached_group_attention(
                    q, kr, vr, positions, cfg.attention_window, cfg.num_heads
                )
        elif self.decode:
            # Fixed-shape cache: [batch, max_seq, kv_heads, head_dim] — the
            # cache holds UN-expanded kv heads (the GQA memory win).
            batch = hidden.shape[0]
            shape = (batch, cfg.max_seq, cfg.kv_heads, cfg.head_dim)
            idx = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            cur = idx.value
            if cfg.quant_kv:
                # int8 cache slabs + per-(token, head) scales.  Scales init
                # to 0, so never-written slots dequantize to exactly 0 (and
                # are masked below regardless).
                ck = self.variable("cache", "cached_key", jnp.zeros, shape, jnp.int8)
                cv = self.variable("cache", "cached_value", jnp.zeros, shape, jnp.int8)
                sshape = (batch, cfg.max_seq, cfg.kv_heads)
                cks = self.variable(
                    "cache", "cached_key_scale", jnp.zeros, sshape, jnp.float32
                )
                cvs = self.variable(
                    "cache", "cached_value_scale", jnp.zeros, sshape, jnp.float32
                )
                # Same fused K/V pair quantization as the paged append.
                kq, vq, ks, vs = quantize_kv_pair(k, v)
                ck.value = jax.lax.dynamic_update_slice(ck.value, kq, (0, cur, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(cv.value, vq, (0, cur, 0, 0))
                cks.value = jax.lax.dynamic_update_slice(cks.value, ks, (0, cur, 0))
                cvs.value = jax.lax.dynamic_update_slice(cvs.value, vs, (0, cur, 0))
            else:
                ck = self.variable("cache", "cached_key", jnp.zeros, shape, k.dtype)
                cv = self.variable("cache", "cached_value", jnp.zeros, shape, v.dtype)
                ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, cur, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, cur, 0, 0))
            idx.value = cur + hidden.shape[1]
            q_len = hidden.shape[1]
            if q_len > 1 and self.append_mode == "auto":
                # Bulk prefill (static branch): attend causally WITHIN the
                # provided tokens via the same non-decode path training
                # uses — O(q_len²) (flash-tiled when 128-aligned) instead
                # of an [q_len, max_seq] score tensor against the whole
                # cache.  K/V still land in the cache above.  A multi-token
                # append into a non-empty cache is outside this contract
                # (greedy_generate only prefills from an empty cache).
                qh, kh, vh = (
                    t.transpose(0, 2, 1, 3) for t in (q, k, v)
                )
                attn = tiled_causal_attention(qh, kh, vh, cfg.attention_window)
                attn = attn.transpose(0, 2, 1, 3).reshape(
                    batch, q_len, cfg.num_heads, cfg.head_dim
                )
            else:
                if cfg.quant_kv:
                    k = dequantize_kv(ck.value, cks.value, cfg.dtype)
                    v = dequantize_kv(cv.value, cvs.value, cfg.dtype)
                else:
                    k, v = ck.value, cv.value
                # Cache-append decode: mask slots at or beyond each query's
                # position; the kv cache is read once per kv head (grouped
                # einsum, never expanded group×) — decode is KV-cache-
                # bandwidth-bound, so this is where GQA's HBM win lands.
                attn = cached_group_attention(
                    q, k, v, positions, cfg.attention_window, cfg.num_heads
                )
        else:
            qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            seq_len = hidden.shape[1]
            if self.attention_fn is not None:
                if group > 1 and not getattr(
                    self.attention_fn, "supports_gqa", False
                ):
                    # MHA-only sp engines (Ulysses: heads ride the
                    # all_to_all) need expanded kv; the ring engine is
                    # GQA-native and advertises supports_gqa, keeping the
                    # rotating kv shard group-times smaller on the ICI ring.
                    kh = jnp.repeat(kh, group, axis=1)
                    vh = jnp.repeat(vh, group, axis=1)
                if cfg.attention_window is not None:
                    # The sp engines compute full causal attention; silently
                    # training full-window while decode masks to the window
                    # would be a train/inference mismatch.
                    raise ValueError(
                        "attention_window is not supported with a custom "
                        "attention_fn (sequence-parallel engines are full-"
                        "causal); unset one of them"
                    )
                attn = self.attention_fn(qh, kh, vh, causal=True)
            else:
                attn = tiled_causal_attention(qh, kh, vh, cfg.attention_window)
            attn = attn.transpose(0, 2, 1, 3)

        return _site_call(
            dense_site(cfg, cfg.hidden_size, axis=(-2, -1), name="out"),
            attn,
            cfg,
            adapter_ids,
        )


class SwiGluMlp(nn.Module):
    """SwiGLU feed-forward: silu(gate(x)) * up(x) -> down."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        cfg = self.config
        gate = _site_call(
            dense_site(cfg, cfg.intermediate_size, name="gate"), x, cfg, adapter_ids
        )
        up = _site_call(
            dense_site(cfg, cfg.intermediate_size, name="up"), x, cfg, adapter_ids
        )
        down = _site_call(
            dense_site(cfg, cfg.hidden_size, name="down"),
            nn.silu(_scaled(gate, cfg.multipliers.mlp_gate)) * up,
            cfg,
            adapter_ids,
        )
        return _scaled(down, cfg.multipliers.mlp_down)


class DecoderBlock(nn.Module):
    config: GPTConfig
    decode: bool = False
    mlp_factory: Optional[Any] = None  # swap-in point for MoE (parallel/moe.py)
    attention_fn: Optional[Any] = None
    append_mode: str = "auto"

    @nn.compact
    def __call__(self, hidden, positions, adapter_ids=None, last_positions=None):
        cfg, mul = self.config, self.config.multipliers
        normed = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="attn_norm")(hidden)
        attn = CausalSelfAttention(
            cfg,
            decode=self.decode,
            attention_fn=self.attention_fn,
            append_mode=self.append_mode,
            name="attn",
        )(
            _scaled(normed, mul.attention_in),
            positions,
            adapter_ids,
        )
        hidden = hidden + _scaled(attn, mul.attention_out)
        if cfg.mixer is not None:
            # The same pre-norm feeds the mixer, side by side with attention.
            mixed = Mamba2Mixer(cfg, decode=self.decode, name="mixer")(
                _scaled(normed, mul.ssm_in), positions, last_positions
            )
            hidden = hidden + _scaled(mixed, mul.ssm_out)
        if cfg.lora_serve and self.mlp_factory is not None:
            # A swapped-in MLP (MoE) has the plain one-argument call and
            # would silently skip its adapters.
            raise ValueError("lora_serve is not supported with mlp_factory")
        mlp_mod = (
            self.mlp_factory() if self.mlp_factory is not None else SwiGluMlp(cfg, name="mlp")
        )
        norm_h = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="mlp_norm")(hidden)
        mlp = (
            mlp_mod(norm_h, adapter_ids) if cfg.lora_serve else mlp_mod(norm_h)
        )
        return hidden + mlp


class ShortcutBlock(nn.Module):
    """One block of a shortcut-connected pair (``cfg.moe``).  With ``x`` the
    pair's input, ``A`` attention, ``F`` the dense SwiGLU, ``M`` the expert
    layer::

        a = x + A_0(norm x);   u = norm a;   m = M(u);   y = a + F_0(u)        first block
        z = y + A_1(norm y);   out = z + F_1(norm z) + m                       second block

    The first block (``first``) returns ``(y, m)``, the second takes ``m``
    as ``pending`` and returns ``(out, None)``.  Parameter and cache names
    are ``DecoderBlock``'s (``attn_norm``, ``attn``, ``mlp_norm``, ``mlp``)
    with ``moe`` beside them in a first block."""

    config: GPTConfig
    first: bool
    decode: bool = False
    append_mode: str = "auto"

    @nn.compact
    def __call__(self, hidden, positions, pending=None, token_mask=None):
        cfg = self.config
        normed = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="attn_norm")(hidden)
        hidden = hidden + LatentAttention(
            cfg, decode=self.decode, append_mode=self.append_mode, name="attn"
        )(normed, positions)
        u = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="mlp_norm")(hidden)
        if self.first:
            pending = ExpertLayer(cfg, name="moe")(u, token_mask)
            return hidden + SwiGluMlp(cfg, name="mlp")(u), pending
        return hidden + SwiGluMlp(cfg, name="mlp")(u) + pending, None


class TransformerLM(nn.Module):
    """Decoder-only LM: embed -> N pre-norm blocks -> RMSNorm -> vocab logits.

    ``__call__(input_ids)`` returns [batch, seq, vocab] float32 logits.  In
    ``decode`` mode pass ``positions`` (absolute positions of the provided
    tokens) and keep the ``cache`` collection mutable.
    """

    config: GPTConfig
    decode: bool = False
    mlp_factory: Optional[Any] = None
    attention_fn: Optional[Any] = None
    append_mode: str = "auto"

    @nn.compact
    def __call__(
        self, input_ids, positions=None, output: str = "logits", adapter_ids=None,
        last_positions=None, logits_at=None, token_mask=None,
    ):
        """``last_positions`` [batch]: each row's last REAL position, for a
        model whose mixer carries state through the sequence (positions
        past it are padding the state must not see); attention is causal
        and needs none.  ``logits_at`` [batch]: an index into the sequence
        axis a row; only that position goes through the head, and the
        logits come back as [batch, 1, vocab].  ``token_mask`` [batch,
        seq] bool: the real tokens, for a model with expert layers (an idle
        slot's or a padded position's row routes nowhere and counts
        nothing; None = every token is real)."""
        cfg = self.config
        seq_len = input_ids.shape[-1]
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(seq_len)[None, :], input_ids.shape
            )
        if cfg.lora_serve and adapter_ids is None:
            # Base-model default so init/eval_shape paths need no vector;
            # the serving engine always passes its per-slot ids.
            adapter_ids = jnp.full((input_ids.shape[0],), -1, jnp.int32)
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="embed")(
            input_ids
        )
        hidden = _scaled(hidden, cfg.multipliers.embedding)
        block_cls = (
            nn.remat(DecoderBlock, static_argnums=()) if cfg.remat else DecoderBlock
        )
        pending = None  # a pair's expert output, on its way to the pair's end
        for i in range(cfg.num_layers):
            if cfg.moe is not None:
                if self.mlp_factory is not None or self.attention_fn is not None or cfg.remat:
                    raise ValueError("cfg.moe (ShortcutBlock) takes no mlp_factory, attention_fn or remat")
                hidden, pending = ShortcutBlock(
                    cfg, first=i % 2 == 0, decode=self.decode,
                    append_mode=self.append_mode, name=f"layer_{i}",
                )(hidden, positions, pending, token_mask)
                continue
            hidden = block_cls(
                cfg,
                decode=self.decode,
                mlp_factory=self.mlp_factory,
                attention_fn=self.attention_fn,
                append_mode=self.append_mode,
                name=f"layer_{i}",
            )(hidden, positions, adapter_ids, last_positions)
        hidden = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="final_norm")(hidden)
        if output == "hidden":
            # For the fused LM-head + cross-entropy path (ops/fused_xent.py):
            # the caller applies params["lm_head"]["kernel"] chunk-wise so
            # the [batch, seq, vocab] logits tensor never materializes.
            # The head still initializes below on the "logits" path; a
            # "hidden"-only init would miss its params, so init always
            # runs with the default output.
            return hidden
        if output != "logits":
            raise ValueError(f"output must be logits|hidden, got {output!r}")
        if logits_at is not None:
            hidden = jnp.take_along_axis(hidden, logits_at[:, None, None], axis=1)
        # Logits in float32 for a stable softmax/xent.
        logits = _site_call(
            dense_site(cfg, cfg.vocab_size, dtype=jnp.float32, name="lm_head"),
            hidden,
            cfg,
            adapter_ids,
        )
        return _scaled(logits, cfg.multipliers.lm_head)


def decode_cache_spec(model: TransformerLM, batch: int):
    """Shape/dtype tree of ``model``'s decode cache for ``batch`` rows,
    computed abstractly (no params materialize).  Call OUTSIDE jit and
    build zeros from it inside — shared by the decode loop here and the
    speculative loop (models/speculative.py)."""
    return jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch, 1), jnp.int32),
            jnp.zeros((batch, 1), jnp.int32),
        )["cache"]
    )


@lru_cache(maxsize=16)
def _compiled_decode(
    config: GPTConfig,
    batch: int,
    prompt_len: int,
    max_new_tokens: int,
    temperature: float | None = None,
    top_k: int | None = None,
):
    """Build (once per shape/config) the jitted greedy-decode loop.

    jit caches are keyed on the function object, so defining the closure
    inside every generate call would retrace and recompile the whole decode
    scan each time — the round-1 decode benchmark was timing compiles, not
    decoding (ADVICE r1).  Caching the closure here makes repeat calls hit
    the compiled executable.
    """
    model = TransformerLM(config, decode=True)
    # init() runs a forward pass, which writes its dummy token into the cache
    # and advances cache_index — we only need the structure; the zeros are
    # created inside `run` (from ShapeDtypeStructs, so no large host constant
    # is baked into the compiled program).
    cache_spec = decode_cache_spec(model, batch)

    def pick(logits, key):
        """Next-token selection from [batch, vocab] logits — greedy when no
        temperature, else temperature(+top-k) categorical sampling.  The
        branch is STATIC (part of the compile cache key), so the compiled
        scan contains exactly one selection path."""
        if temperature is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / max(temperature, 1e-6)
        if top_k is not None:
            kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, NEG_LOGIT, scaled)
        return jax.random.categorical(key, scaled).astype(jnp.int32)

    @jax.jit
    def run(params, prompt, rng):
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_spec)

        # Bulk prefill: ONE forward over the whole prompt writes all of its
        # K/V into the cache (the multi-token decode path masks per query
        # position, so causality inside the prompt is preserved).  This is
        # the TPU-shaped prefill — a [batch, prompt_len] matmul-heavy pass
        # on the MXU instead of prompt_len tiny steps through the scan.
        pos = jnp.broadcast_to(jnp.arange(prompt_len), (batch, prompt_len))
        logits, mut = model.apply(
            {"params": params, "cache": cache}, prompt, pos, mutable=["cache"]
        )
        cache = mut["cache"]
        first = pick(
            logits[:, -1, :], jax.random.fold_in(rng, prompt_len - 1)
        )[:, None]

        # Decode: single-token steps through the cache, scanned under jit.
        def step(carry, t):
            cache, tok = carry
            pos = jnp.broadcast_to(t, (batch, 1))
            logits, mut = model.apply(
                {"params": params, "cache": cache},
                tok,
                pos,
                mutable=["cache"],
            )
            nxt = pick(logits[:, -1, :], jax.random.fold_in(rng, t))[:, None]
            return (mut["cache"], nxt), nxt[:, 0]

        (_, _), toks = jax.lax.scan(
            step,
            (cache, first),
            jnp.arange(prompt_len, prompt_len + max_new_tokens - 1),
        )
        seq = jnp.concatenate([prompt, first, toks.T], axis=1)
        return seq

    return run


def greedy_generate(
    config: GPTConfig,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
) -> jax.Array:
    """Greedy autoregressive decode with the fixed-shape KV cache.

    prompt: [batch, prompt_len] int32.  Returns [batch, prompt_len + new].
    One jitted program: a bulk prefill pass writes the whole prompt's K/V
    into the cache, then a `lax.scan` over single-token decode steps —
    static shapes throughout, no host round-trips; the compiled program is
    cached per (config, batch, prompt_len, max_new_tokens) so repeated
    calls don't recompile.
    """
    batch, prompt_len = prompt.shape
    _check_decode_fits(config, prompt_len, max_new_tokens)
    return _compiled_decode(config, batch, prompt_len, max_new_tokens)(
        params, prompt, jax.random.PRNGKey(0)  # unused by the greedy path
    )


def sample_generate(
    config: GPTConfig,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    *,
    rng: jax.Array,
    temperature: float = 1.0,
    top_k: int | None = None,
) -> jax.Array:
    """Stochastic autoregressive decode: temperature (+ optional top-k)
    categorical sampling through the same cached/prefilled scan as
    :func:`greedy_generate` — the sampler is a static branch in the
    compiled program, keyed into the compile cache alongside the shapes.

    Deterministic given ``rng`` (keys are folded per position), so runs are
    reproducible and batch elements draw independent tokens.
    """
    if temperature <= 0:
        raise ValueError(
            f"temperature must be > 0, got {temperature}; use greedy_generate "
            "for argmax decoding"
        )
    if top_k is not None and not 1 <= top_k <= config.vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={config.vocab_size}], got {top_k}"
        )
    batch, prompt_len = prompt.shape
    _check_decode_fits(config, prompt_len, max_new_tokens)
    return _compiled_decode(
        config, batch, prompt_len, max_new_tokens, float(temperature), top_k
    )(params, prompt, rng)


def _check_decode_fits(config: GPTConfig, prompt_len: int, max_new_tokens: int):
    if prompt_len + max_new_tokens > config.max_seq:
        # dynamic_update_slice would silently clamp cache writes past
        # max_seq, overwriting the last slot — fail loudly instead.
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq {config.max_seq}"
        )
