"""Fused multi-head attention (flash attention) as a Pallas TPU kernel.

The reference delegates all compute to its workload image (SURVEY.md §2.4:
"GPU compute kernels — absent from the plugin; delegated to the workload");
our workload layer is first-party, so its hot op gets a first-party TPU
kernel.  Design follows the TPU flash-attention pattern (online softmax with
running max/denominator, one [block_q, block_kv] tile resident in VMEM at a
time), NOT a port of any CUDA kernel:

- grid = (batch*heads, q_blocks, kv_blocks); the kv axis is innermost, which
  TPU executes sequentially per (batch, q_block), so the running softmax
  state lives in VMEM scratch across kv iterations.
- tiles are MXU-shaped ([128, 128] blocks by default); both matmuls
  (q·kᵀ and p·v) accumulate in float32 via preferred_element_type while
  inputs stay bfloat16.
- with ``causal=True`` tiles entirely above the diagonal skip both matmuls
  (`pl.when` guard) — ~2x fewer MXU FLOPs at long sequence length.
- O(seq) memory: the [seq, seq] score matrix never exists in HBM, which is
  what lets long-context models fit (HBM capacity/bandwidth is the TPU
  bottleneck, not FLOPs).

Differentiation: the forward also emits per-row log-sum-exp, and the custom
VJP recomputes attention **one kv block at a time** (`lax.scan`) from the
saved q/k/v/out/lse — flash-style rematerialization, O(seq·block) peak
memory in backward too, no [seq, seq] residual ever stored.

On non-TPU backends the same kernel runs under the Pallas interpreter
(tests), or callers use :func:`mha_reference` directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")

# Per-generation (block_q, block_kv) defaults, matched by device_kind
# prefix, separately for forward and backward.  The forward kernel is
# grid-overhead-bound at small tiles on v5e — the round-2 idle-machine
# sweep (median-of-5, 50-iter chains, separate k/v buffers) measured
# q128/kv512 at 2.53 ms vs q512/kv1024 at 1.23 ms for b4 h16 s2048 d64 —
# so the fwd default rides the large end; VMEM stays modest (f32 scores
# tile 512x1024 = 2 MB + double-buffered kv tiles).  The backward kernels
# keep more operands live per tile (q, k, v, dO, O, lse + two f32
# accumulators), so their swept optimum is squarer: the round-3 bwd sweep
# (two-point, 10-iter chains) measured grad(flash) at q512/kv512 in
# 1.67 ms vs 2.78 ms at q256/kv512 for b4 h16 s2048 d64, and 1.49 ms vs
# 7.03 ms (single-point) at the old q128/kv512 for d=128 — q512/kv1024
# regressed (8.15 ms, VMEM pressure), so bwd stays at 512x512.
_BLOCK_DEFAULTS = (
    ("TPU v5 lite", (512, 1024)),
    ("TPU v5e", (512, 1024)),
    ("TPU v5p", (512, 1024)),
    ("TPU v4", (128, 256)),
    ("TPU v6", (512, 1024)),  # unswept: inherit v5e until a v6 sweep exists
)
_BWD_BLOCK_DEFAULTS = (
    ("TPU v5 lite", (512, 512)),
    ("TPU v5e", (512, 512)),
    ("TPU v5p", (512, 512)),
    ("TPU v4", (128, 256)),
    ("TPU v6", (512, 512)),  # unswept: inherit v5e until a v6 sweep exists
)
_FALLBACK_BLOCKS = (128, 256)  # unknown TPU generation
_INTERPRET_BLOCKS = (128, 128)  # CPU interpreter: smallest legal tiles


def _default_blocks(interpret: bool, table=_BLOCK_DEFAULTS) -> tuple[int, int]:
    if interpret or jax.default_backend() != "tpu":
        return _INTERPRET_BLOCKS
    kind = jax.devices()[0].device_kind
    for prefix, blocks in table:
        if kind.startswith(prefix):
            return blocks
    return _FALLBACK_BLOCKS


def _fit_block(block: int, seq: int) -> int:
    """Largest size <= block that divides ``seq`` (halving from block)."""
    b = min(block, seq)
    while b > 1 and seq % b:
        b //= 2
    return b


# Short sequences: the large per-generation forward defaults exist to
# amortize grid setup over LONG kv walks, but at seq <= _SHORT_SEQ the
# naive fit swallows the whole sequence into one or two tiles and
# starves the grid of parallel work — the r03–r05 smoke rows measured
# the (1, 2, 256, 64) forward at 1.38 ms vs XLA's 1.05 ms (0.76x)
# because q512 fitted to a single 256-row tile.  Capping the defaulted
# q block at 128 under the threshold restores >= 2 q-programs per
# (batch, head) and the MXU-native 128-row tile; the kv block keeps its
# fitted size (kv iterations are the sequential axis either way).
# Explicitly-passed blocks are never capped.
_SHORT_SEQ = 512
_SHORT_BLOCK_Q = 128


def _auto_block(default: int, seq: int, q_axis: bool = False) -> int:
    fitted = _fit_block(default, seq)
    if q_axis and seq <= _SHORT_SEQ and fitted > _SHORT_BLOCK_Q:
        # Re-fit from the cap, not min(): the capped block must still
        # divide the sequence (192 fits to 64, not an invalid 128).
        fitted = _fit_block(_SHORT_BLOCK_Q, seq)
    return fitted


def resolve_blocks(
    seq_q: int,
    seq_kv: int,
    block_q: int | None = None,
    block_kv: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_kv: int | None = None,
    interpret: bool = False,
    defaults: tuple[tuple[int, int], tuple[int, int]] | None = None,
) -> tuple[int, int, int, int]:
    """The one block-resolution rule :func:`flash_attention` applies:
    per-generation defaults fitted to the sequence (with the short-seq q
    cap above), explicit blocks clamped but never re-fitted.  Split out
    (and parameterized on ``defaults`` = ((fwd_q, fwd_kv), (bwd_q,
    bwd_kv))) so the chosen tiles are unit-testable off-TPU —
    tests/test_ops.py pins the short-sequence fix."""
    if defaults is None:
        defaults = (
            _default_blocks(interpret),
            _default_blocks(interpret, _BWD_BLOCK_DEFAULTS),
        )
    (default_q, default_kv), (bwd_default_q, bwd_default_kv) = defaults

    def resolve(explicit, default, seq, q_axis=False):
        if explicit is not None:
            return min(explicit, seq)
        return _auto_block(default, seq, q_axis=q_axis)

    return (
        resolve(block_q, default_q, seq_q, q_axis=True),
        resolve(block_kv, default_kv, seq_kv),
        resolve(bwd_block_q, bwd_default_q, seq_q),
        resolve(bwd_block_kv, bwd_default_kv, seq_kv),
    )


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Plain-XLA attention with identical semantics to the kernel.

    [batch, heads, seq, head_dim] in, same out; float32 softmax accumulation.
    The numerical oracle for tests and the non-fused fallback path.
    ``window`` (requires causal): each query attends to the ``window`` most
    recent positions, itself included — Mistral-style local attention.

    Grouped-query attention: k/v may carry ``kv_heads`` dividing q's heads;
    being the oracle (not the fast path), this simply expands kv heads.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if k.shape[1] != q.shape[1]:
        if q.shape[1] % k.shape[1]:
            raise ValueError(
                f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
            )
        group = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            # window=0 would mask every score; softmax over all -inf is NaN.
            raise ValueError(f"window must be >= 1, got {window}")
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
        mask = row >= col
        if window is not None:
            mask = jnp.logical_and(mask, row - col < window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


# --------------------------------------------------------------------- kernel


def _tile_live(qi, ki, block_q: int, block_kv: int, window):
    """Whether a [block_q, block_kv] tile intersects the causal(+window)
    band: its smallest column must not exceed its largest row, and with a
    window its largest column must not fall entirely behind the smallest
    row's window.  Shared by the forward and both backward kernels."""
    live = (qi * block_q + block_q - 1) >= (ki * block_kv)
    if window is not None:
        live = jnp.logical_and(
            live,
            (ki * block_kv + block_kv - 1) >= (qi * block_q - (window - 1)),
        )
    return live


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    sm_scale: float,
    causal: bool,
    window,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    qi = pl.program_id(1)

    def _tile():
        # Inputs stay in their storage dtype (bfloat16 in production):
        # the MXU multiplies bf16 natively with float32 accumulation via
        # preferred_element_type — upcasting q/k/v first would demote both
        # matmuls to the much slower f32 MXU path.
        q = q_ref[0]  # [block_q, head_dim]
        k = k_ref[0]  # [block_kv, head_dim]
        v = v_ref[0]

        # Scores tile on the MXU, float32 accumulation.
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * sm_scale
        )  # [block_q, block_kv]

        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = row >= col
            if window is not None:
                mask = jnp.logical_and(mask, row - col < window)
            s = jnp.where(mask, s, NEG_INF)

        # Online softmax update.  m/l scratch is [block_q, 128]
        # (lane-replicated: TPU vector registers are 128 lanes wide, a
        # [block_q, 1] store would be sub-lane); only column 0 is read back.
        m_prev = m_ref[:, :1]  # [block_q, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked-so-far rows keep m_new == -inf; exp(-inf - -inf) would
        # be NaN, so substitute 0 under the mask (they contribute nothing).
        seen = m_new > NEG_INF
        p = jnp.where(seen, jnp.exp(s - jnp.where(seen, m_new, 0.0)), 0.0)
        alpha = jnp.where(seen, jnp.exp(jnp.where(seen, m_prev - m_new, 0.0)), 0.0)

        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # p·v on the MXU in the inputs' dtype (bf16 weights path); the
        # f32 statistics (m/l/acc) keep the online softmax exact.
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Dead tiles skip both matmuls (the grid still visits them —
        # Pallas grids are rectangular — but they cost only this check).
        pl.when(_tile_live(qi, ki, block_q, block_kv, window))(_tile)
    else:
        _tile()

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        m = m_ref[...]  # [block_q, 128], lane-replicated
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked row -> zero output
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        # Per-row log-sum-exp, the backward pass's softmax residual.  Written
        # lane-replicated ([block_q, 128]) — a [block_q, 1] -> [1, block_q]
        # transpose would be a cross-lane shuffle; callers read lane 0.
        lse_ref[0] = jnp.where(l > 0.0, m + jnp.log(l_safe), NEG_INF)


def _flash_impl(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    window,
    sm_scale: float,
    block_q: int,
    block_kv: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [b,h,sq,d], lse_rep [b*h, sq, 128] float32).

    The returned log-sum-exp is the kernel's lane-replicated layout (every
    lane carries the row's value); the backward kernels read it directly
    as (1, block_q, 128) tiles, so no cross-lane reshape ever happens.

    GQA-native: k/v may have ``kv_heads`` dividing q's ``heads``.  The kv
    BlockSpec index map routes every q head to its group's kv head, so the
    kv tile is *shared* across the head group in VMEM — no repeated K/V is
    ever materialized in HBM and the kernel does kv_heads' worth of kv
    traffic, not heads' (the GQA bandwidth win the round-1 `jnp.repeat`
    path gave away).
    """
    batch, heads, seq_q, head_dim = q.shape
    kv_heads, seq_kv = k.shape[1], k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"q heads {heads} not a multiple of kv heads {kv_heads}")
    group = heads // kv_heads
    _check_blocks(seq_q, seq_kv, block_q, block_kv)
    bh = batch * heads
    q3 = q.reshape(bh, seq_q, head_dim)
    k3 = k.reshape(batch * kv_heads, seq_kv, head_dim)
    v3 = v.reshape(batch * kv_heads, seq_kv, head_dim)
    num_q_blocks = seq_q // block_q
    num_kv_blocks = seq_kv // block_kv

    def kv_index(b, qi, ki):
        # Flat q index b = batch_i * heads + head_i; its kv row is
        # batch_i * kv_heads + head_i // group.  Static ints, traced fine.
        return (b // heads) * kv_heads + (b % heads) // group, ki, 0

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",  # stable in a device trace
        grid=(bh, num_q_blocks, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, head_dim), kv_index),
            pl.BlockSpec((1, block_kv, head_dim), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
            # Lane-replicated lse (see kernel); lane 0 is sliced off below.
            jax.ShapeDtypeStruct((bh, seq_q, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denominator
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # output accumulator
        ],
        # Mosaic grid semantics: bh and q blocks are independent (parallel);
        # the kv axis carries the online-softmax scratch between iterations
        # and must stay sequential (arbitrary).  Telling the compiler lets it
        # overlap/pipeline the parallel axes instead of serializing the grid.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(batch, heads, seq_q, head_dim), lse


# ------------------------------------------------------------------- backward


def _check_blocks(seq_q: int, seq_kv: int, block_q: int, block_kv: int) -> None:
    if seq_q % block_q or seq_kv % block_kv:
        raise ValueError(
            f"seq lengths ({seq_q}, {seq_kv}) must divide by blocks "
            f"({block_q}, {block_kv}); pad to MXU multiples first"
        )


def _bwd_p_tile(q, k, lse_col, rows, cols, sm_scale, causal, window):
    """Recompute the probability tile P = exp(S·scale − lse) with masking.

    Shared by both backward kernels.  ``lse_col`` is [block_q, 1] float32;
    rows/cols are absolute index iotas for the tile.  Returns p
    ([block_q, block_kv] float32).
    """
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * sm_scale
    )
    # Rows that attended to nothing carry lse == -inf; exp(s - -inf) would
    # be +inf, so force their P to 0 via the finite mask.
    finite = lse_col > NEG_INF
    p = jnp.where(finite, jnp.exp(s - jnp.where(finite, lse_col, 0.0)), 0.0)
    if causal:
        mask = rows >= cols
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
        p = jnp.where(mask, p, 0.0)
    return p


def _dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    o_ref,
    lse_ref,
    dq_ref,
    dq_acc,
    *,
    sm_scale: float,
    causal: bool,
    window,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    """dQ: grid (b*h, q_blocks, kv_blocks), kv innermost sequential.

    Flash-style recomputation: P is rebuilt one kv tile at a time from the
    saved lse (never [seq, seq]); dQ accumulates in a float32 VMEM scratch
    across the kv axis and is written once on the last kv block.
    """
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        cols = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        lse_col = lse_ref[0][:, :1]
        p = _bwd_p_tile(q, k, lse_col, rows, cols, sm_scale, causal, window)
        # delta_i = Σ_d dO·O per row — cheap enough to recompute per tile
        # (block_q·d mul-adds vs the block_q·block_kv·d matmuls around it).
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(_tile_live(qi, ki, block_q, block_kv, window))(_tile)
    else:
        _tile()

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    o_ref,
    lse_ref,
    dk_ref,
    dv_ref,
    dk_acc,
    dv_acc,
    *,
    sm_scale: float,
    causal: bool,
    window,
    block_q: int,
    block_kv: int,
    num_q_blocks: int,
    group: int,
):
    """dK/dV: grid (b*kv_heads, kv_blocks, group*q_blocks), innermost
    sequential over the whole (q-head-in-group × q-block) range.

    GQA-native like the forward: one kv tile stays resident while every q
    head of its group streams past, so the shared kv head's gradient sums
    the whole group without any repeated K/V in HBM.
    """
    ki, t = pl.program_id(1), pl.program_id(2)
    qi = t % num_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        cols = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        lse_col = lse_ref[0][:, :1]
        p = _bwd_p_tile(q, k, lse_col, rows, cols, sm_scale, causal, window)
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dK += dSᵀ·Q, dV += Pᵀ·dO — contract the q-row axis (dim 0 of both).
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_tile_live(qi, ki, block_q, block_kv, window))(_tile)
    else:
        _tile()

    @pl.when(t == group * num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(
    q, k, v, out, lse_rep, dout, causal, window, sm_scale, block_q, block_kv, interpret
):
    """Fused flash backward: two Pallas kernels (dQ; dK/dV), both O(seq)
    memory, both GQA-native.  lse_rep is the forward's lane-replicated
    [b*h, seq_q, 128] residual — consumed tile-wise, no reshapes."""
    batch, heads, seq_q, head_dim = q.shape
    kv_heads, seq_kv = k.shape[1], k.shape[2]
    group = heads // kv_heads
    _check_blocks(seq_q, seq_kv, block_q, block_kv)
    bh = batch * heads
    q3 = q.reshape(bh, seq_q, head_dim)
    do3 = dout.reshape(bh, seq_q, head_dim)
    o3 = out.reshape(bh, seq_q, head_dim)
    k3 = k.reshape(batch * kv_heads, seq_kv, head_dim)
    v3 = v.reshape(batch * kv_heads, seq_kv, head_dim)
    num_q_blocks = seq_q // block_q
    num_kv_blocks = seq_kv // block_kv

    def kv_index(b, qi, ki):
        return (b // heads) * kv_heads + (b % heads) // group, ki, 0

    q_spec = pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0))
    kv_spec = pl.BlockSpec((1, block_kv, head_dim), kv_index)
    lse_spec = pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            sm_scale=sm_scale,
            causal=causal,
            window=window,
            block_q=block_q,
            block_kv=block_kv,
            num_kv_blocks=num_kv_blocks,
        ),
        name="flash_attention_bwd_dq",
        grid=(bh, num_q_blocks, num_kv_blocks),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        out_specs=pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3, do3, o3, lse_rep)

    # dK/dV grid walks (kv head, kv block, every group member × q block);
    # index maps route each t to its q row within the group.
    def q_row(b2, ki, t):
        g = t // num_q_blocks
        return (b2 // kv_heads) * heads + (b2 % kv_heads) * group + g

    def q_index(b2, ki, t):
        return q_row(b2, ki, t), t % num_q_blocks, 0

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            sm_scale=sm_scale,
            causal=causal,
            window=window,
            block_q=block_q,
            block_kv=block_kv,
            num_q_blocks=num_q_blocks,
            group=group,
        ),
        name="flash_attention_bwd_dkv",
        grid=(batch * kv_heads, num_kv_blocks, group * num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_index),
            pl.BlockSpec((1, block_kv, head_dim), lambda b2, ki, t: (b2, ki, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda b2, ki, t: (b2, ki, 0)),
            pl.BlockSpec((1, block_q, head_dim), q_index),
            pl.BlockSpec((1, block_q, head_dim), q_index),
            pl.BlockSpec((1, block_q, 128), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, head_dim), lambda b2, ki, t: (b2, ki, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda b2, ki, t: (b2, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * kv_heads, seq_kv, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch * kv_heads, seq_kv, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3, do3, o3, lse_rep)

    return (
        dq.reshape(q.shape),
        dk.reshape(k.shape),
        dv.reshape(v.shape),
    )


def _mha_bwd_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    causal: bool,
    window,
    sm_scale: float,
    block_kv: int,
):
    """Flash-style backward: recompute P one kv block at a time from the
    saved lse, never materializing [seq, seq].

    Standard decomposition (same math every flash backward uses):
        Pᵢⱼ = exp(Sᵢⱼ·scale − lseᵢ)
        Dᵢ  = Σⱼ dOᵢⱼ·Oᵢⱼ            (row dot, O(seq·d))
        dPᵢⱼ = dO·Vᵀ ;  dSᵢⱼ = Pᵢⱼ·(dPᵢⱼ − Dᵢ)·scale
        dQ = ΣⱼdS·K ;  dK = dSᵀ·Q ;  dV = Pᵀ·dO
    Each kv block contributes independently, so a `lax.scan` over kv blocks
    accumulates dQ and emits the block's dK/dV — peak extra memory is one
    [seq_q, block_kv] tile per (batch, head), i.e. O(seq), matching forward.

    GQA: q (and out/dout/lse) carry ``heads = kv_heads * group``; all
    row-indexed tensors are reshaped to an explicit [b, kv_heads, group, …]
    layout so each einsum contracts q's group axis against the *shared* kv
    head — dK/dV sum a whole head group's contribution in one matmul and
    no repeated K/V exists.
    """
    f32 = jnp.float32
    batch, heads, seq_q, head_dim = q.shape
    kv_heads, seq_kv = k.shape[1], k.shape[2]
    group = heads // kv_heads
    g5 = (batch, kv_heads, group, seq_q, head_dim)
    g4 = (batch, kv_heads, group, seq_q)
    qf = q.astype(f32).reshape(g5)
    dof = dout.astype(f32).reshape(g5)
    of = out.astype(f32).reshape(g5)
    kf, vf = k.astype(f32), v.astype(f32)
    num_blocks = seq_kv // block_kv

    d_row = jnp.sum(dof * of, axis=-1)  # [b,hk,g,sq]
    # Rows that attend to nothing have lse == -inf; exp(s - -inf) would blow
    # up, so clamp (their P is forced to 0 below anyway via the finite mask).
    lse = lse.reshape(g4)
    finite = jnp.isfinite(lse)
    lse_safe = jnp.where(finite, lse, 0.0)

    # With a sliding window only rows [start, start + block_kv - 1 + window)
    # can touch kv block [start, start + block_kv) — slice just that query
    # band (static length) so backward FLOPs scale O(seq·window) like the
    # forward's tile skipping, instead of masking a dense [seq_q, block_kv].
    banded = (
        causal
        and window is not None
        and seq_q == seq_kv  # band geometry assumes aligned self-attention
        and block_kv + window - 1 < seq_q
    )
    q_rows = min(seq_q, block_kv + window - 1) if banded else seq_q

    row_ids = jax.lax.broadcasted_iota(jnp.int32, (q_rows, block_kv), 0)

    def one_block(dq_acc, block_idx):
        start = block_idx * block_kv
        k_blk = jax.lax.dynamic_slice_in_dim(kf, start, block_kv, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(vf, start, block_kv, axis=2)
        if banded:
            # Clamped band start: rows [row0, row0 + q_rows) cover every
            # in-band row for this kv block.  Sequence is axis 3 in the
            # grouped [b, kv_heads, group, seq, ...] layout.
            row0 = jnp.minimum(start, seq_q - q_rows)
            q_b = jax.lax.dynamic_slice_in_dim(qf, row0, q_rows, axis=3)
            do_b = jax.lax.dynamic_slice_in_dim(dof, row0, q_rows, axis=3)
            dr_b = jax.lax.dynamic_slice_in_dim(d_row, row0, q_rows, axis=3)
            lse_b = jax.lax.dynamic_slice_in_dim(lse_safe, row0, q_rows, axis=3)
            fin_b = jax.lax.dynamic_slice_in_dim(finite, row0, q_rows, axis=3)
            rows_abs = row0 + row_ids
        else:
            row0 = 0
            q_b, do_b, dr_b, lse_b, fin_b = qf, dof, d_row, lse_safe, finite
            rows_abs = row_ids
        # h = kv head, g = q-head group member: kv tensors have no g axis,
        # so XLA broadcasts one kv tile across the group (GQA-native).
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_b, k_blk) * sm_scale
        p = jnp.exp(s - lse_b[..., None])
        p = jnp.where(fin_b[..., None], p, 0.0)
        if causal:
            col_ids = start + jax.lax.broadcasted_iota(
                jnp.int32, (q_rows, block_kv), 1
            )
            mask = rows_abs >= col_ids
            if window is not None:
                mask = jnp.logical_and(mask, rows_abs - col_ids < window)
            p = jnp.where(mask, p, 0.0)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", do_b, v_blk)
        ds = p * (dp - dr_b[..., None]) * sm_scale
        dq_contrib = jnp.einsum("bhgqk,bhkd->bhgqd", ds, k_blk)
        if banded:
            cur = jax.lax.dynamic_slice_in_dim(dq_acc, row0, q_rows, axis=3)
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc, cur + dq_contrib, row0, axis=3
            )
        else:
            dq_acc = dq_acc + dq_contrib
        # dK/dV contract the group axis too: the shared kv head's gradient
        # sums every q head in its group in one matmul.
        dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, q_b)
        dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", p, do_b)
        return dq_acc, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        one_block, jnp.zeros_like(qf), jnp.arange(num_blocks)
    )
    # scan stacks along axis 0: [nblocks, b, hk, block_kv, d] -> [b, hk, skv, d]
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(v.shape)
    return dq.reshape(q.shape).astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(
    q, k, v, causal, window, sm_scale, block_q, block_kv,
    bwd_block_q, bwd_block_kv, interpret, bwd_impl,
):
    out, _ = _flash_impl(
        q, k, v, causal, window, sm_scale, block_q, block_kv, interpret
    )
    return out


def _flash_fwd(
    q, k, v, causal, window, sm_scale, block_q, block_kv,
    bwd_block_q, bwd_block_kv, interpret, bwd_impl,
):
    out, lse_rep = _flash_impl(
        q, k, v, causal, window, sm_scale, block_q, block_kv, interpret
    )
    if bwd_impl != "pallas":
        # The XLA backward only reads one lane — slice the residual down to
        # [b, h, seq] here rather than holding the 128x lane-replicated
        # buffer live between forward and backward for every layer.
        batch, heads, seq_q = q.shape[0], q.shape[1], q.shape[2]
        return out, (q, k, v, out, lse_rep[:, :, 0].reshape(batch, heads, seq_q))
    return out, (q, k, v, out, lse_rep)


def _flash_bwd(
    causal, window, sm_scale, block_q, block_kv, bwd_block_q, bwd_block_kv,
    interpret, bwd_impl, residuals, dout,
):
    q, k, v, out, lse = residuals
    if bwd_impl == "pallas":
        # lse is the lane-replicated [b*h, seq, 128] layout (see _flash_fwd).
        return _flash_bwd_pallas(
            q, k, v, out, lse, dout,
            causal, window, sm_scale, bwd_block_q, bwd_block_kv, interpret,
        )
    return _mha_bwd_chunked(
        q, k, v, out, lse, dout, causal, window, sm_scale, bwd_block_kv
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_kv: int | None = None,
    interpret: bool | None = None,
    bwd_impl: str = "auto",
) -> jax.Array:
    """Fused attention over [batch, heads, seq, head_dim] inputs.

    Grouped-query attention is native: pass k/v with ``kv_heads`` dividing
    q's ``heads`` and each q-head group reads its shared kv tile directly —
    kv HBM traffic scales with kv_heads, not heads, in forward AND backward.

    ``interpret`` defaults to running the compiled kernel on TPU and the
    Pallas interpreter elsewhere (so the same code path is testable on the
    8-device CPU mesh).  ``block_q``/``block_kv`` tile the FORWARD kernel
    and ``bwd_block_q``/``bwd_block_kv`` the backward kernels; each
    defaults per TPU generation (``_BLOCK_DEFAULTS`` /
    ``_BWD_BLOCK_DEFAULTS``, keyed on device_kind; 128/128 under the
    interpreter) and clamps to the sequence length for short sequences.
    The passes tile independently because their VMEM working sets differ
    (backward keeps q, k, v, dO, O, lse and two f32 accumulators live per
    tile) — a forward-fast shape like 512x2048 is not automatically safe
    or fast for backward.

    ``window`` (requires ``causal``): sliding-window local attention — each
    query sees only its ``window`` most recent positions.  Forward tiles
    entirely outside the band skip both matmuls, and the chunked backward
    restricts each kv block to its query band, so both passes scale
    O(seq·window) instead of O(seq²) once seq >> window.

    ``bwd_impl``: "pallas" — fused flash backward kernels (dQ; dK/dV),
    "xla" — the chunked `lax.scan` backward, "auto" (default) — pallas on
    TPU, xla elsewhere (the interpreter is too slow for the bwd grids in
    routine test runs; dedicated parity tests exercise the pallas path).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if bwd_impl == "auto":
        bwd_impl = "xla" if interpret else "pallas"
    if bwd_impl not in ("pallas", "xla"):
        raise ValueError(f"bwd_impl must be auto|pallas|xla, got {bwd_impl!r}")
    # Defaulted blocks FIT the sequence (halve until they divide it) so a
    # generation default of 512 never rejects a seq that 128 accepted —
    # and short sequences additionally cap the forward q block so the
    # grid keeps parallel work (resolve_blocks; the r03–r05 short-seq
    # regression).  Explicitly-passed blocks keep the strict
    # divide-or-raise contract.
    fwd_q, fwd_kv, bwd_q, bwd_kv = resolve_blocks(
        q.shape[2], k.shape[2], block_q, block_kv,
        bwd_block_q, bwd_block_kv, interpret,
    )
    return _flash(
        q, k, v, causal, window, sm_scale, fwd_q, fwd_kv, bwd_q, bwd_kv,
        interpret, bwd_impl,
    )
