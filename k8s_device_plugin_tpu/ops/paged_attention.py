"""Split-K flash-decode paged attention: page-table-indirected KV pools
streamed once with online softmax, partitioned across a split axis.

The gather path (models/transformer.py paged decode) materializes every
slot's logical [max_len] K/V view in HBM before the attention einsum —
correct, but it writes (and re-reads) max_len bytes per slot per step even
when a sequence occupies two pages.  This kernel reads pages DIRECTLY from
the pool: the page table rides Pallas's scalar-prefetch lane, so each grid
step's BlockSpec index map picks its physical page (`table[b, page]`) and
the DMA engine streams the pages a slot points at — no intermediate view.

Split-K (the flash-decode shape, new in this round): decode attention has
ONE query per slot, so the page axis is the only parallelism available —
and the previous kernel walked it sequentially, serializing a long
context behind one program.  Now each sequence's page list is partitioned
across a ``num_splits`` grid axis: every program computes a partial
``(running max m, denominator l, unnormalized accumulator acc)`` over its
page span with online softmax, and a cheap second-stage combine reduces
the partials exactly:

    m* = max_s m_s;   alpha_s = exp(m_s - m*)
    out = (sum_s alpha_s * acc_s) / (sum_s alpha_s * l_s)

Short contexts pick the degenerate 1-split (ops/tuning.py), which skips
the combine entirely and emits the normalized output straight from the
kernel — the previous single-pass behavior.

Quantized pools dequantize INSIDE the kernel, never in HBM:

- int8 pools stream as int8 with per-(slot, head) scale pools riding as
  extra blocks; the scale factors out of the head_dim dot, so pages
  matmul on the exact int8→compute-dtype cast and scales multiply the
  small score matrix (the gather path materializes a full dequantized
  [max_len] view first — the traffic this fusion deletes);
- int4-packed pools (two signed nibbles per byte along head_dim,
  ops/quant.py ``quantize_kv4``) unpack in VMEM with sign-extending
  shifts — a QUARTER of the bf16 page bytes; same score-side scales.

Backend routing: on TPU the Pallas kernel compiles under Mosaic.  On CPU
(the engine's parity/smoke environment) the SAME split-K math runs as a
vectorized XLA program (``_decode_xla``) — algebraically identical
(same split partition, same online-softmax/combine associativity), which
is what took the CPU smoke rows from the old Pallas-interpreter's
0.06–0.12x of the gather path to >=1x (the KERNELS ledger,
`benchmark.py --kernel`).  Passing ``interpret=True`` still forces the
real kernel through the Pallas interpreter — that is the parity lane for
the kernel itself (tests/test_paged_attention.py), not a serving path.

Status: lowered by Mosaic and run on a v5e under jax 0.9.0 (chip run,
PR 21) at the shipped pool geometry — page 16, 4 kv heads, group 4, head
size 64, 32 pages a row, batch 8 — for float, int8 and int4 pools at 1
and 8 splits, agreeing with the gather path to bf16 rounding
(chip_smoke.py's kernel leg repeats the check).  Its speed against the
gather path is not measured, so `use_kernel` stays opt-in
(docs/kernels.md "Fallback & parity contract", ROADMAP Speed 5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning

NEG_INF = float("-inf")

# TPU vector registers are 8 sublanes x 128 lanes; a q tile shorter than 8
# rows would be sub-sublane, so the head group is padded up to this.
_MIN_GROUP_TILE = 8

def _unpack_int4(packed: jax.Array, dtype) -> jax.Array:
    """Sign-extend an int4-packed array (two nibbles per int8 byte along
    the last axis; element 2i in the LOW nibble) to ``dtype`` with twice
    the last-dim width.  Plain shifts + one interleave reshape — works
    identically in the Pallas kernel, the interpreter, and the XLA
    route, so every backend computes the same bytes."""
    x = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(x, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(x, 24), 28)
    both = jnp.stack([lo, hi], axis=-1)  # [..., d/2, 2]
    return both.reshape(*packed.shape[:-1], packed.shape[-1] * 2).astype(dtype)


def _combine_splits(o_part, m_part, l_part, out_dtype):
    """Second-stage reduction over the split axis (axis 1).

    ``o_part``: [batch, splits, kv_heads, group, head_dim] f32 unnormalized
    accumulators; ``m_part``/``l_part``: [batch, splits, kv_heads, group]
    f32 running max / denominator.  Empty splits carry (m=-inf, l=0,
    acc=0) and contribute exactly nothing; a row with NO live split (a
    fully-masked query — the engine never produces one, lens >= 1)
    returns zeros rather than NaN.
    """
    m_star = jnp.max(m_part, axis=1, keepdims=True)  # [b, 1, hk, g]
    seen = m_part > NEG_INF
    alpha = jnp.where(
        seen, jnp.exp(jnp.where(seen, m_part - m_star, 0.0)), 0.0
    )
    denom = jnp.sum(alpha * l_part, axis=1)  # [b, hk, g]
    out = jnp.sum(alpha[..., None] * o_part, axis=1)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    return (out / denom[..., None]).astype(out_dtype)


def _page_update(
    q_ref, k_ref, v_ref, sk_ref, sv_ref, m_ref, l_ref, acc_ref,
    *, p_abs, length, lo, page_size: int, kv_heads: int, sm_scale: float,
    window, quant: bool, int4: bool,
):
    """Online-softmax update of the VMEM state triple with one resident
    page (all kv heads), shared by the 1-split and split-K kernels.
    ``p_abs`` is the page's ABSOLUTE index in the row's logical order —
    masking is positional, so splits never change the math."""
    # f32 operands need HIGHEST or the MXU's bf16 passes cost ~2e-3.
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None
    # Mask positions at/past the frontier (the partial last page) and,
    # under a sliding window, positions that scrolled out — the mask is
    # head-independent, so it is built once outside the unroll.
    group_pad = q_ref.shape[-2]
    col = p_abs * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (group_pad, page_size), 1
    )
    valid = col < length
    if window is not None:
        valid = jnp.logical_and(valid, col >= lo)
    for h in range(kv_heads):  # static unroll: one page, every kv head
        q = q_ref[0, h]  # [group_pad, head_dim]
        k = k_ref[0, :, h, :]  # [page_size, head_dim(/2 packed)]
        v = v_ref[0, :, h, :]
        if int4:
            # int4 pages: two sign-extended nibbles per byte unpack in
            # VMEM — a quarter of the bf16 page traffic; scales factor
            # onto the score matrix exactly like int8's.
            k = _unpack_int4(k, q.dtype)
        elif quant:
            # int8 pages: the per-(position, head) scale factors OUT of
            # the dot over head_dim, so the page matmuls on the EXACT
            # int8→compute-dtype cast (|x| <= 127 is exact in bf16) and
            # the scale multiplies the small [group_pad, page_size]
            # score matrix in f32 — no dequantized page materializes.
            k = k.astype(q.dtype)
        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec,
            )
            * sm_scale
        )  # [group_pad, page_size]
        if quant:
            s = s * sk_ref[0, h][None, :]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h, :, :1]
        l_prev = l_ref[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        seen = m_new > NEG_INF
        prob = jnp.where(seen, jnp.exp(s - jnp.where(seen, m_new, 0.0)), 0.0)
        alpha = jnp.where(
            seen, jnp.exp(jnp.where(seen, m_prev - m_new, 0.0)), 0.0
        )
        l_ref[h] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(prob, axis=-1, keepdims=True),
            l_ref.shape[1:],
        )
        if int4:
            prob = prob * sv_ref[0, h][None, :]
            v = _unpack_int4(v, q.dtype)
        elif quant:
            # V's scale rides the probabilities (same factoring as K).
            prob = prob * sv_ref[0, h][None, :]
            v = v.astype(q.dtype)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            prob.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec,
        )
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])


def _paged_kernel(
    table_ref,  # scalar-prefetch: [batch, splits * pages_per_split] int32
    lens_ref,  # scalar-prefetch: [batch] int32
    q_ref,  # [1, kv_heads, group_pad, head_dim]
    k_ref,  # [1, page_size, kv_heads, head_dim(/2)] — one full page
    v_ref,
    *rest,  # quant: sk_ref, sv_ref [1, kv_heads, page_size] f32; then the
    # outputs (1-split: o_ref [1, kv_heads, group_pad, head_dim]; split-K:
    # o_ref [1, 1, kv_heads, group_pad, head_dim] f32 partial +
    # m/l partial refs [1, 1, kv_heads, group_pad, 128] f32), then VMEM
    # scratch m/l [kv_heads, group_pad, 128] + acc [kv_heads, group_pad,
    # head_dim] f32
    page_size: int,
    pages_per_split: int,
    num_splits: int,
    kv_heads: int,
    sm_scale: float,
    window,
    quant: bool,
    int4: bool,
):
    if quant:
        sk_ref, sv_ref = rest[0], rest[1]
        rest = rest[2:]
    else:
        sk_ref = sv_ref = None
    if num_splits == 1:
        o_ref, m_ref, l_ref, acc_ref = rest
        mo_ref = lo_ref = None
    else:
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = rest
    b, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    p_abs = s * pages_per_split + p
    length = lens_ref[b]  # valid cache slots: positions [0, length)
    # Sliding window: the (single) query sits at position length-1 and sees
    # keys in (length-1-window, length-1] — i.e. col >= length - window —
    # matching the gather path's `q_pos - key_pos < window` mask
    # (models/transformer.py cached_group_attention).
    lo = length - window if window is not None else 0

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # Pages wholly past the frontier — or wholly scrolled out of the
    # window — skip all matmuls (the grid is rectangular; dead pages cost
    # one predicate).  Split padding pages land here too: their absolute
    # position starts at/past max_len >= length.
    live = p_abs * page_size < length
    if window is not None:
        live = jnp.logical_and(live, (p_abs + 1) * page_size > lo)
    pl.when(live)(
        lambda: _page_update(
            q_ref, k_ref, v_ref, sk_ref, sv_ref, m_ref, l_ref, acc_ref,
            p_abs=p_abs, length=length, lo=lo, page_size=page_size,
            kv_heads=kv_heads, sm_scale=sm_scale, window=window,
            quant=quant, int4=int4,
        )
    )

    @pl.when(p == pages_per_split - 1)
    def _finish():
        if num_splits == 1:
            # Degenerate split: normalize in-kernel, no combine stage.
            for h in range(kv_heads):
                l = l_ref[h, :, :1]
                l_safe = jnp.where(l == 0.0, 1.0, l)
                o_ref[0, h] = (acc_ref[h] / l_safe).astype(o_ref.dtype)
        else:
            # Emit this split's partial triple; _combine_splits reduces.
            o_ref[0, 0] = acc_ref[...]
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]


def _paged_pallas(
    q4, pool_k, pool_v, table, lens, scale_k, scale_v,
    *, sm_scale, window, num_splits, quant, int4, interpret,
):
    """The Pallas lane: compiled under Mosaic on TPU, interpreter when
    ``interpret`` (the kernel-parity tests).  ``q4`` is [batch, kv_heads,
    group_pad, head_dim] with the group padded to the sublane tile."""
    batch, kv_heads, group_pad, head_dim = q4.shape
    page_size = pool_k.shape[1]
    mpp = table.shape[1]
    pages_per_split = -(-mpp // num_splits)
    if pages_per_split * num_splits != mpp:
        # Pad the table so every split spans the same page count; padding
        # entries alias page 0 (the engine's scratch page — repeated
        # indices skip re-fetch) and their absolute positions start at
        # >= max_len, so the dead-page predicate skips their compute.
        table = jnp.pad(
            table, ((0, 0), (0, pages_per_split * num_splits - mpp))
        )
    kernel = functools.partial(
        _paged_kernel,
        page_size=page_size,
        pages_per_split=pages_per_split,
        num_splits=num_splits,
        kv_heads=kv_heads,
        sm_scale=sm_scale,
        window=window,
        quant=quant,
        int4=int4,
    )
    q_spec = pl.BlockSpec(
        (1, kv_heads, group_pad, head_dim),
        lambda b, s, p, table, lens: (b, 0, 0, 0),
    )
    page_spec = pl.BlockSpec(
        (1, page_size, kv_heads, pool_k.shape[3]),
        lambda b, s, p, table, lens: (
            table[b, s * pages_per_split + p], 0, 0, 0,
        ),
    )
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q4, pool_k, pool_v]
    if quant:
        # Scales ride as [pool, kv_heads, page_size] so the in-kernel
        # slice [0, h] lands on the LANE axis, matching the score
        # matrix's page_size lanes (the engine stores [pool, page_size,
        # kv_heads]; this transpose moves KB, the pools move MB).
        scale_spec = pl.BlockSpec(
            (1, kv_heads, page_size),
            lambda b, s, p, table, lens: (
                table[b, s * pages_per_split + p], 0, 0,
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [
            jnp.swapaxes(scale_k, 1, 2),
            jnp.swapaxes(scale_v, 1, 2),
        ]
    if num_splits == 1:
        out_specs = pl.BlockSpec(
            (1, kv_heads, group_pad, head_dim),
            lambda b, s, p, table, lens: (b, 0, 0, 0),
        )
        out_shape = jax.ShapeDtypeStruct(
            (batch, kv_heads, group_pad, head_dim), q4.dtype
        )
    else:
        part_spec = pl.BlockSpec(
            (1, 1, kv_heads, group_pad, head_dim),
            lambda b, s, p, table, lens: (b, s, 0, 0, 0),
        )
        ml_spec = pl.BlockSpec(
            (1, 1, kv_heads, group_pad, 128),
            lambda b, s, p, table, lens: (b, s, 0, 0, 0),
        )
        out_specs = [part_spec, ml_spec, ml_spec]
        out_shape = [
            jax.ShapeDtypeStruct(
                (batch, num_splits, kv_heads, group_pad, head_dim),
                jnp.float32,
            ),
            jax.ShapeDtypeStruct(
                (batch, num_splits, kv_heads, group_pad, 128), jnp.float32
            ),
            jax.ShapeDtypeStruct(
                (batch, num_splits, kv_heads, group_pad, 128), jnp.float32
            ),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, num_splits, pages_per_split),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kv_heads, group_pad, 128), jnp.float32),
            pltpu.VMEM((kv_heads, group_pad, 128), jnp.float32),
            pltpu.VMEM((kv_heads, group_pad, head_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_decode",  # stable in a device trace
        grid_spec=grid_spec,
        out_shape=out_shape,
        # batch and split axes are independent; the page axis carries the
        # online-softmax scratch between iterations (sequential).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(table, lens, *operands)
    if num_splits == 1:
        return out
    o_part, m_part, l_part = out
    return _combine_splits(
        o_part, m_part[..., 0], l_part[..., 0], q4.dtype
    )


def _decode_xla(
    q4, pool_k, pool_v, table, lens, scale_k, scale_v,
    *, sm_scale, window, num_splits, quant, int4,
):
    """The XLA lane: the SAME split-K online-softmax math as the kernel,
    vectorized over the split axis — the CPU serving/parity route (and
    the reference the interpreter parity suite checks the kernel
    against).  ``q4`` is [batch, kv_heads, group, head_dim] UNPADDED
    (no tile constraints off-chip)."""
    batch, kv_heads, group, head_dim = q4.shape
    page_size = pool_k.shape[1]
    mpp = table.shape[1]
    prec = jax.lax.Precision.HIGHEST if q4.dtype == jnp.float32 else None
    splits = num_splits
    pps = -(-mpp // splits)
    if pps * splits != mpp:
        table = jnp.pad(table, ((0, 0), (0, pps * splits - mpp)))
    span = pps * page_size  # positions per split
    # One page-indexed gather per pool — the same bytes the gather path
    # reads, but nothing dequantized is ever materialized at [max_len]
    # width: integer codes cast inside the fused attention computation
    # and scales multiply the score matrix, not the operands.
    k = pool_k[table].reshape(batch, splits, span, kv_heads, -1)
    v = pool_v[table].reshape(batch, splits, span, kv_heads, -1)
    if int4:
        k = _unpack_int4(k, q4.dtype)
        v = _unpack_int4(v, q4.dtype)
    elif k.dtype != q4.dtype:
        k = k.astype(q4.dtype)
        v = v.astype(q4.dtype)
    s = jnp.einsum(
        "bhgd,bslhd->bshgl", q4, k,
        preferred_element_type=jnp.float32, precision=prec,
    ) * sm_scale  # [b, S, hk, g, span]
    if quant:
        sk = scale_k[table].reshape(batch, splits, span, kv_heads)
        s = s * sk.transpose(0, 1, 3, 2)[:, :, :, None, :]
    col = jnp.arange(splits * span, dtype=jnp.int32).reshape(splits, span)
    col = col[None, :, None, None, :]
    ln = lens[:, None, None, None, None]
    valid = col < ln
    if window is not None:
        valid = jnp.logical_and(valid, col >= ln - window)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # per-split running max
    seen = m > NEG_INF
    p = jnp.where(seen, jnp.exp(s - jnp.where(seen, m, 0.0)), 0.0)
    l = jnp.sum(p, axis=-1)  # [b, S, hk, g]
    if quant:
        sv = scale_v[table].reshape(batch, splits, span, kv_heads)
        p = p * sv.transpose(0, 1, 3, 2)[:, :, :, None, :]
    acc = jnp.einsum(
        "bshgl,bslhd->bshgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32, precision=prec,
    )
    return _combine_splits(acc, m[..., 0], l, q4.dtype)


def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_table: jax.Array,
    lens: jax.Array,
    *,
    scale_k: jax.Array | None = None,
    scale_v: jax.Array | None = None,
    sm_scale: float | None = None,
    window: int | None = None,
    num_splits: int | None = None,
    kv_format: str | None = None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Single-token decode attention over a paged KV pool (split-K).

    q: [batch, num_heads, head_dim] — the current token's queries.
    pool_k/pool_v: [num_pool_pages, page_size, kv_heads, head_dim] —
    float pools, int8 pools, or int4-packed pools ([..., head_dim//2]
    int8, two signed nibbles per byte; ops/quant.py ``quantize_kv4``).
    page_table: [batch, pages_per_seq] int32 physical page ids.
    lens: [batch] int32 — valid cache slots per row (the current token's
    K/V must already be written: ``lens = position + 1``).

    Returns [batch, num_heads, head_dim].  GQA-native: ``kv_heads`` must
    divide ``num_heads``; each group shares its kv head's resident page.

    ``window``: sliding attention window — the query sees only the last
    ``window`` positions (same semantics as the gather path); pages
    wholly outside it skip compute, and the serving engine additionally
    re-points their table entries at scratch so they skip fetch too.

    ``num_splits``: how many grid programs partition each row's page
    list (None = the per-generation tuning table, ops/tuning.py — 1 on
    CPU and for short contexts, where the combine stage is skipped
    entirely).  The split changes float association only through the
    documented combine; every split count computes the same attention.

    ``kv_format``: None infers "f" (float pools) or "int8" from the pool
    dtype; pass "int4" for packed pools (also auto-inferred when the
    pool's trailing dim is head_dim//2).  Quantized formats require
    ``scale_k``/``scale_v`` pools [num_pool_pages, page_size, kv_heads].

    ``use_pallas``/``interpret``: None routes TPU to the compiled Mosaic
    kernel and everything else to the vectorized XLA implementation of
    the same math; ``interpret=True`` forces the real kernel through the
    Pallas interpreter (the kernel-parity lane).

    Traffic note: table entries past a row's live pages are read by the
    pipeline regardless of the dead-page predicate — point them all at
    one scratch page to keep per-row traffic O(len).  models/engine.py
    does exactly this (idle rows, window-reclaimed entries, and
    not-yet-written generation pages all alias scratch page 0).
    """
    batch, num_heads, head_dim = q.shape
    kv_heads, page_size = pool_k.shape[2], pool_k.shape[1]
    pages_per_seq = page_table.shape[1]
    if num_heads % kv_heads:
        raise ValueError(
            f"num_heads {num_heads} not a multiple of kv_heads {kv_heads}"
        )
    if pool_v.dtype != pool_k.dtype or pool_v.shape != pool_k.shape:
        raise ValueError(
            f"pools must match, got k={pool_k.dtype}{pool_k.shape} "
            f"v={pool_v.dtype}{pool_v.shape}"
        )
    if kv_format is None:
        if pool_k.dtype == jnp.int8:
            kv_format = (
                "int4" if pool_k.shape[3] * 2 == head_dim else "int8"
            )
        else:
            kv_format = "f"
    if kv_format not in ("f", "int8", "int4"):
        raise ValueError(f"kv_format must be f|int8|int4, got {kv_format!r}")
    int4 = kv_format == "int4"
    quant = kv_format in ("int8", "int4")
    if quant and pool_k.dtype != jnp.int8:
        raise ValueError(
            f"{kv_format} pools must be int8 storage, got {pool_k.dtype}"
        )
    want_last = head_dim // 2 if int4 else head_dim
    if int4 and head_dim % 2:
        raise ValueError(f"int4 packing needs even head_dim, got {head_dim}")
    if pool_k.shape[3] != want_last:
        raise ValueError(
            f"pool head_dim {pool_k.shape[3]} != expected {want_last} for "
            f"kv_format={kv_format!r} (int4 pools pack two values per byte)"
        )
    if quant and (scale_k is None or scale_v is None):
        raise ValueError(
            f"{kv_format} pools require scale_k and scale_v scale pools"
        )
    if not quant and (scale_k is not None or scale_v is not None):
        raise ValueError(
            f"scale pools passed with {pool_k.dtype} (non-int8) pools"
        )
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    group = num_heads // kv_heads
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu or bool(interpret)
    if interpret is None:
        interpret = not on_tpu
    if num_splits is None:
        num_splits = tuning.pick_num_splits(pages_per_seq)
    num_splits = max(1, min(int(num_splits), pages_per_seq))

    q4 = q.reshape(batch, kv_heads, group, head_dim)
    if not use_pallas:
        out = _decode_xla(
            q4, pool_k, pool_v, page_table, lens, scale_k, scale_v,
            sm_scale=sm_scale, window=window, num_splits=num_splits,
            quant=quant, int4=int4,
        )
        return out.reshape(batch, num_heads, head_dim)

    group_pad = max(group, _MIN_GROUP_TILE)
    if group_pad != group:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, group_pad - group), (0, 0)))
    out = _paged_pallas(
        q4, pool_k, pool_v, page_table, lens, scale_k, scale_v,
        sm_scale=sm_scale, window=window, num_splits=num_splits,
        quant=quant, int4=int4, interpret=interpret,
    )
    return out[:, :, :group, :].reshape(batch, num_heads, head_dim)
