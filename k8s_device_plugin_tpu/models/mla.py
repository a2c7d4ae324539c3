"""Multi-head latent attention: low-rank queries, ONE low-rank latent for
keys and values, a decoupled rotary key shared by all heads.

Per token (``h`` hidden, ``H`` heads, ranks ``r_q``/``r_kv``, head widths
``d_n`` no-rope / ``d_r`` rope / ``d_v`` value)::

    c_q = norm(x W_qa) * scale_q            [q_n | q_r] = c_q W_qb     per head
    [c | k_r] = x W_kva                     c^ = norm(c) * scale_kv    (k_r is not scaled)
    [k_n | v] = c^ W_kvb  per head          rope on q_r (every head) and k_r (one key)
    score = (q_n.k_n + q_r.k_r) / sqrt(d_n + d_r),  causal softmax,  o = sum p v

What a token leaves in the cache is ``[c^ | rope(k_r)]``: ``r_kv + d_r``
wide (576 where a 64-head K/V row would be 16,384), never the per-head keys
and values.  The row is STORED lane-aligned: zero lanes pad it to a
multiple of 128 (``[c^ | rope(k_r) | 0]``, 640 wide), so a TPU keeps the
pool row-major.  A 576-wide row is no multiple of 128 lanes: row-major
tiles would pad it to 640 anyway, so the chip's compact layout would put
the pages minor, and every decode program and graft would lay the whole
pool out row-major for its scatter and gather and back.  The pad lanes
reach no contraction: the gathered path contracts ``[..., :r_kv + d_r]``
and ``[..., :r_kv]``, the latent kernel zeroes them in VMEM.  Two compute
paths over one set of parameters:

- EXPANDED (``mla.expand``): per-head ``k_n`` and ``v`` through ``W_kvb``,
  then ordinary causal attention.  For a bulk prefill into an empty cache
  and for a call without a cache: the tokens' own latents are at hand and
  ``d_n + d_r`` = 192 per score beats the latent's 576.
- ABSORBED (``mla.absorb``): with ``W_kvb = [W_uk | W_uv]`` per head,
  ``q~ = q_n W_uk^T`` (r_kv wide), ``score = (q~.c^ + q_r.k_r)/sqrt(d_n+d_r)``,
  ``o~ = sum p c^``, ``o = o~ W_uv``: the cache row is one key and one
  value for all heads and is never expanded.  For decode and for every
  append that attends against a cache: expanding 64 slots x 900 cached
  positions a step would cost 60x the step's other FLOPs.

The absorbed path over a PAGED pool has two lanes between the same two
projections (``q~`` and ``o = o~ W_uv``, XLA einsums in ``mla.absorb``):

- the latent kernel (ops/latent_attention.py, ``latent_decode_attention``):
  a decode of one query a row on a TPU backend reads each live row of the
  pool once, in place through the page table;
- the gather (``paged_gather``, then ``absorbed_attention``): every other
  case, an append of several queries a row against the pool and every
  backend but a TPU (the CPU parity environment).  It writes the
  ``[batch, max_len, W]`` view of every addressable position.

The lane is read off what the program can observe (``latent_kernel_lane``:
the backend and the append's length), never set.

Three cache forms, as ``CausalSelfAttention`` has them: none; dense
``cached_latent [batch, max_seq, W]`` with ``cache_index`` (the engine's
prefill bridge); paged ``pool_latent [num_pages, page_size, W]`` with
``page_table`` / ``seq_lens``; ``W`` is ``MlaConfig.stored_width``.  The
serving engine's compiled cache writers, page copies and snapshots walk
``pool_*`` / ``cached_*`` leaves of any trailing shape, so a latent pool
rides them as a K/V pair does (models/engine_paging.py): both forms hold
the same padded row, and every writer copies its zero lanes along.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.expert_ffn import on_kernel_lane
from ..ops.latent_attention import latent_decode_attention

# Scores of one absorbed append are [rows, heads, q_len, cache] float32, and
# the latent-space queries and outputs [rows, q_len, heads, r_kv]: rows
# beyond this many score elements are taken in turn (lax.map), so a prefill
# chunk of 64 x 256 against 512 cached positions holds 0.5 GB of scores and
# 0.6 GB of queries and outputs at a time, not 2 and 3.3.
_SCORE_ELEMS = 1 << 27

# A TPU vector register's lanes: the minor dimension of a tile.
LANES = 128


@dataclass(frozen=True)
class MlaConfig:
    """Shape of latent attention (a published config's ``*_lora_rank`` and
    ``qk_*_head_dim`` / ``v_head_dim`` keys).  ``scale_q`` / ``scale_kv``
    multiply the normed query / key-value latents (1.0: nothing is)."""

    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    scale_q: float = 1.0
    scale_kv: float = 1.0

    @property
    def row_width(self) -> int:
        """Width of a token's cache row: the latent and the rotary key."""
        return self.kv_rank + self.rope_dim

    @property
    def stored_width(self) -> int:
        """Width of a cache row as stored: ``row_width`` rounded up to whole
        lanes, the rest zeros.  A row that is already lane-aligned is
        stored as it is."""
        return -(-self.row_width // LANES) * LANES

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim {self.rope_dim} must be even (rotary pairs)")


def absorbed_attention(q_n, q_r, latent, positions, kv_b, sm_scale: float):
    """Attention of queries moved into the latent space against cache rows.

    q_n [batch, q_len, heads, d_n], q_r [batch, q_len, heads, d_r] (rotated);
    latent [batch, L, >= r_kv + d_r] (``c^ | rope(k_r)``, then any pad
    lanes, which no contraction reads: a dense cache or a gathered page
    view); kv_b [r_kv, heads, d_n + d_v]; a query at
    ``positions[b, i]`` sees rows ``<= position``.  Returns the heads'
    outputs [batch, q_len, heads, d_v].  Everything that is heads x r_kv
    wide (``q~``, the scores, ``o~``) lives inside one block of rows."""
    from .transformer import NEG_LOGIT

    r_kv, d_n = kv_b.shape[0], q_n.shape[-1]
    width = r_kv + q_r.shape[-1]

    def rows(q_n, q_r, lat, pos):
        q_lat = jnp.concatenate([jnp.einsum("bqhd,chd->bqhc", q_n, kv_b[..., :d_n]), q_r], axis=-1)
        key_pos = jnp.arange(lat.shape[1])[None, None, None, :]
        s = jnp.einsum("bqhc,bkc->bhqk", q_lat, lat[..., :width], preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(key_pos <= pos[:, None, :, None], s, NEG_LOGIT)
        p = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
        o_lat = jnp.einsum("bhqk,bkc->bqhc", p, lat[..., :r_kv])
        return jnp.einsum("bqhc,chd->bqhd", o_lat, kv_b[..., d_n:])

    batch, q_len, heads, _ = q_n.shape
    block = max(1, _SCORE_ELEMS // (heads * q_len * latent.shape[1]))
    if batch <= block:
        return rows(q_n, q_r, latent, positions)
    return jax.lax.map(
        lambda t: rows(*(x[None] for x in t))[0], (q_n, q_r, latent, positions), batch_size=block
    )


def latent_kernel_lane(q_len: int) -> bool:
    """Whether a paged append of ``q_len`` queries a row reads the pool
    through the latent kernel: one query a row (a decode step) on a TPU
    backend.  A fact of the program, known before it is compiled."""
    return q_len == 1 and on_kernel_lane()


def absorbed_decode(q_n, q_r, pool, table, positions, kv_b, sm_scale: float):
    """``absorbed_attention`` of one query a row read in place from a paged
    pool: q_n [batch, 1, heads, d_n], q_r [batch, 1, heads, d_r] (rotated);
    pool [num_pages, page_size, W]; table [batch, max_pages]; the query at
    ``positions[b, 0]`` sees rows ``<= position``.  Returns [batch, 1,
    heads, d_v].  The projections stay einsums; the kernel computes ``o~``
    from the rows where they live."""
    r_kv, d_n = kv_b.shape[0], q_n.shape[-1]
    q_lat = jnp.concatenate([jnp.einsum("bhd,chd->bhc", q_n[:, 0], kv_b[..., :d_n]), q_r[:, 0]], axis=-1)
    key_width = q_lat.shape[-1]
    q_lat = jnp.pad(q_lat, ((0, 0), (0, 0), (0, pool.shape[-1] - key_width)))
    lengths = jnp.clip(positions[:, 0] + 1, 1, table.shape[1] * pool.shape[1])
    o_lat = latent_decode_attention(q_lat, pool, table, lengths, key_width=key_width, kv_rank=r_kv, sm_scale=sm_scale)
    return jnp.einsum("bhc,chd->bhd", o_lat, kv_b[..., d_n:])[:, None]


class LatentAttention(nn.Module):
    """Latent attention of one block; ``config`` is the model's
    ``GPTConfig`` with ``config.mla`` an ``MlaConfig``.  Same call and
    same cache discipline as ``CausalSelfAttention``."""

    config: Any
    decode: bool = False
    append_mode: str = "auto"

    @nn.compact
    def __call__(self, hidden, positions, adapter_ids=None):
        from .transformer import RMSNorm, apply_rope, dense_site, rope_angles, tiled_causal_attention

        cfg, mc = self.config, self.config.mla
        if self.append_mode not in ("auto", "cached"):
            raise ValueError(f"append_mode must be auto|cached, got {self.append_mode!r}")
        if cfg.quant is not None or cfg.quant_kv or cfg.lora_rank is not None or cfg.lora_serve:
            raise ValueError(
                "latent attention (cfg.mla) is not supported with quant, quant_kv or "
                "LoRA: the absorbed path reads kv_b's kernel itself, and a cache row "
                "has no heads to scale by"
            )
        if cfg.attention_window is not None:
            raise ValueError("latent attention (cfg.mla) has no sliding window")
        batch, q_len, _ = hidden.shape
        heads, r_kv, d_n, d_r, d_v = cfg.num_heads, mc.kv_rank, mc.nope_dim, mc.rope_dim, mc.v_dim
        sm_scale = (d_n + d_r) ** -0.5

        with jax.named_scope("mla.project"):
            c_q = dense_site(cfg, mc.q_rank, name="q_a")(hidden)
            c_q = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="q_norm")(c_q)
            if mc.scale_q != 1.0:
                c_q = c_q * jnp.asarray(mc.scale_q, cfg.dtype)
            q = dense_site(cfg, (heads, d_n + d_r), name="q_b")(c_q)  # [b, q, H, d_n + d_r]
            kv = dense_site(cfg, r_kv + d_r, name="kv_a")(hidden)
            latent = RMSNorm(dtype=cfg.dtype, eps=cfg.rms_norm_eps, name="kv_norm")(kv[..., :r_kv])
            if mc.scale_kv != 1.0:
                latent = latent * jnp.asarray(mc.scale_kv, cfg.dtype)
            cos, sin = rope_angles(positions, d_r, cfg.rope_theta)
            q_n, q_r = q[..., :d_n], apply_rope(q[..., d_n:], cos, sin)
            k_r = apply_rope(kv[..., None, r_kv:], cos, sin)[:, :, 0]  # one key for all heads
            row = jnp.concatenate([latent, k_r], axis=-1)  # the token's cache row
            if mc.stored_width > mc.row_width:  # stored lane-aligned, zero lanes last
                row = jnp.pad(row, ((0, 0), (0, 0), (0, mc.stored_width - mc.row_width)))
        # [r_kv, H, d_n + d_v]: a plain parameter, read whole by the
        # expanded path and in its two halves by the absorbed one.
        kv_b = self.param(
            "kv_b", nn.initializers.normal(r_kv ** -0.5), (r_kv, heads, d_n + d_v)
        ).astype(cfg.dtype)

        def expanded():
            """Causal attention among the provided tokens."""
            with jax.named_scope("mla.expand"):
                kvh = jnp.einsum("bsc,chd->bshd", latent, kv_b)
                k = jnp.concatenate(
                    [kvh[..., :d_n], jnp.broadcast_to(k_r[:, :, None], (batch, q_len, heads, d_r))], axis=-1
                )
                # One kernel serves q, k and v of one width: the values
                # ride zero-padded to the keys' and are cut back after.
                v = jnp.pad(kvh[..., d_n:], ((0, 0),) * 3 + ((0, d_n + d_r - d_v),))
                qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (jnp.concatenate([q_n, q_r], axis=-1), k, v))
                out = tiled_causal_attention(qh, kh, vh, None)
                return out.transpose(0, 2, 1, 3)[..., :d_v]

        def absorbed(cache_rows):
            """Attention against stored cache rows [batch, L, stored_width]."""
            with jax.named_scope("mla.absorb"):
                return absorbed_attention(q_n, q_r, cache_rows, positions, kv_b, sm_scale)

        if self.decode and cfg.paged is not None:
            pg = cfg.paged
            if pg.use_kernel:
                raise ValueError(
                    "use_kernel is not supported with latent attention (cfg.mla): the K/V "
                    "paged-attention kernel (ops/paged_attention.py) takes key and value "
                    "pools; latent attention's decode has a kernel lane of its own "
                    "(ops/latent_attention.py), taken on a TPU backend"
                )
            pool = self.variable(
                "cache", "pool_latent", jnp.zeros, (pg.num_pages, pg.page_size, mc.stored_width), row.dtype
            )
            table = self.variable("cache", "page_table", jnp.zeros, (batch, pg.max_pages_per_seq), jnp.int32)
            lens = self.variable("cache", "seq_lens", jnp.zeros, (batch,), jnp.int32)
            cur = lens.value  # first written position per row
            # q_len consecutive positions a row through the table (idle
            # rows land in scratch page 0, masked forever).
            offs = cur[:, None] + jnp.arange(q_len)[None, :]
            page = table.value[jnp.arange(batch)[:, None], offs // pg.page_size]
            pool.value = pool.value.at[page, offs % pg.page_size].set(row)
            lens.value = cur + q_len
            if latent_kernel_lane(q_len):
                with jax.named_scope("mla.absorb"):
                    attn = absorbed_decode(q_n, q_r, pool.value, table.value, positions, kv_b, sm_scale)
            else:
                with jax.named_scope("paged_gather"):
                    rows = pool.value[table.value].reshape(batch, pg.max_len, mc.stored_width)
                attn = absorbed(rows)
        elif self.decode:
            idx = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            cached = self.variable(
                "cache", "cached_latent", jnp.zeros, (batch, cfg.max_seq, mc.stored_width), row.dtype
            )
            cur = idx.value
            cached.value = jax.lax.dynamic_update_slice(cached.value, row, (0, cur, 0))
            idx.value = cur + q_len
            if q_len > 1 and self.append_mode == "auto":
                attn = expanded()  # bulk prefill into an empty cache
            else:
                attn = absorbed(cached.value)
        else:
            attn = expanded()
        return dense_site(cfg, cfg.hidden_size, axis=(-2, -1), name="out")(attn)


def mla_scale(flag, hidden_size: int, rank: int) -> float:
    """A published ``mla_scale_*_lora`` key as a factor: true reads
    sqrt(hidden_size / rank), false 1."""
    return math.sqrt(hidden_size / rank) if flag else 1.0
