"""Absorbed latent-attention decode read in place from a paged latent pool.

One query a slot (a decode step), all heads moved into the latent space
(models/mla.py, ``mla.absorb``)::

    q_lat [batch, heads, W]   = [q_n W_uk^T | q_r | 0]
    pool  [num_pages, page_size, W]: a cached position's row [c^ | rope(k_r) | 0]
    score = q_lat . row[:key_width] * sm_scale      (key_width = r_kv + d_r)
    o_lat = sum softmax(score) row[:kv_rank]

where slot ``b`` sees rows ``0 .. lengths[b] - 1`` through
``page_table[b]``.  The XLA lane (models/mla.py ``absorbed_attention``
over ``pool[page_table]``) writes every addressable position of every
slot into a gathered view and reads it twice; this kernel reads each live
row from HBM once and the view never exists.

**Grid and DMA.** One program a slot, sequential (``"arbitrary"``): the
program walks its slot's live pages in blocks of ``PAGES_PER_BLOCK`` with
an in-kernel loop.  Each block is one async copy a page, HBM straight into
one half of a double buffer in VMEM, the next block in flight while this
one is computed; the slot's last block starts the NEXT slot's first, so
only the first program waits on a cold fetch.  A full block is waited for
with one wait for all its bytes, the slot's last (partial) block page by
page.  A page past the slot's length is neither fetched nor computed.  The page table (flattened) and
the lengths ride the scalar-prefetch lane; the pool stays in HBM
(``memory_space=pl.ANY``) and is taken as it lives there, with no copy.

**One tile is key and value.** A block's tile ``[rows, W]`` is the key
(lanes ``[:key_width]``) and the value (lanes ``[:kv_rank]``).  Lanes at or
past ``key_width`` and rows at or past the slot's length are zeroed in
VMEM before any contraction: a pad lane, a stale row of the buffer, or a
page the slot does not own can hold anything (NaN included) and reach
nothing.  All heads of the slot are the matmuls' rows.

**Numerics** are ``absorbed_attention``'s in another order of summation:
scores float32, an online softmax (running max, denominator, float32
accumulator) normalised at the end, the probabilities cast to the pool's
dtype for the value matmul, ``o_lat`` in the pool's dtype.

The lane is chosen by the caller (models/mla.py): a TPU backend, a paged
decode of one query a slot.  ``interpret`` None runs the compiled kernel
on a TPU and the Pallas interpreter anywhere else (tests/
test_latent_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Pages a block: 32 pages of 16 rows, 512 rows a block.  Alone at
# longcat-flash-d4e16.agentchat's geometry on a v5e (docs/kernels.md) the
# eight calls of a step took 2.53 ms at 8 pages, 2.05 at 16, 1.59-1.70 at
# 32, 1.67 at 24, 1.69 at 48 and 1.79 at 64: a block's copies are 20 KB
# each and cost about 37 ns apiece, so fewer, longer blocks win until the
# masked rows of a slot's last block cost more arithmetic than they save.
PAGES_PER_BLOCK = 32

_NEG = -1e30  # models/transformer.py NEG_LOGIT: a masked score


def _kernel(
    table_ref, lens_ref,  # scalar prefetch: [batch * max_pages], [batch]
    q_ref,  # [1, heads, W]
    pool_hbm,  # [num_pages, page_size, W], in HBM
    o_ref,  # [1, heads, kv_rank]
    buf, sems, done_ref, m_ref, l_ref, acc_ref,  # scratch
    *, batch: int, max_pages: int, key_width: int, kv_rank: int, sm_scale: float,
):
    b = pl.program_id(0)
    _, ppb, page_size, width = buf.shape
    rows = ppb * page_size
    # float32 operands (the CPU tests) need HIGHEST or a matmul costs ~2e-3.
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    def fetch(s, blk, half, start: bool):
        """Start (or wait for) the copies of the pages of block ``blk`` of
        slot ``s`` that the slot holds, into buffer ``half``.  A full block
        is ``ppb`` copies and ONE wait for all of their bytes."""
        first = blk * ppb
        held = pl.cdiv(lens_ref[s], page_size) - first

        def copy(i):
            page = table_ref[s * max_pages + first + i]
            return pltpu.make_async_copy(pool_hbm.at[page], buf.at[half, i], sems.at[half])

        @pl.when(held >= ppb)
        def _full():
            if start:
                for i in range(ppb):
                    copy(i).start()
            else:
                pltpu.make_async_copy(pool_hbm.at[pl.ds(0, ppb)], buf.at[half], sems.at[half]).wait()

        @pl.when(held < ppb)
        def _part():
            def one(i, carry):
                if start:
                    copy(i).start()
                else:
                    copy(i).wait()
                return carry

            jax.lax.fori_loop(0, held, one, 0)

    @pl.when(b == 0)
    def _first():
        done_ref[0] = 0
        fetch(0, 0, 0, True)

    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    length = lens_ref[b]
    n_blocks = pl.cdiv(length, rows)
    q = q_ref[0]  # [heads, W]
    done = done_ref[0]  # blocks of the earlier slots: this slot's first is in buffer done % 2
    dims = (((1,), (1,)), ((), ()))

    def block(j, carry):
        half = (done + j) % 2
        base = j * rows

        # The next block in flight: this slot's, or the next slot's first.
        @pl.when(j + 1 < n_blocks)
        def _():
            fetch(b, j + 1, 1 - half, True)

        @pl.when((j + 1 == n_blocks) & (b + 1 < batch))
        def _():
            fetch(b + 1, 0, 1 - half, True)

        fetch(b, j, half, False)

        # The slot's last block: rows past its length (the rest of its last
        # page, pages it does not hold, a stale buffer) become zeros.
        @pl.when(base + rows > length)
        def _():
            t = buf[half].reshape(rows, width)
            row = base + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
            buf[half] = jnp.where(row < length, t, jnp.zeros_like(t)).reshape(buf.shape[1:])

        tile = buf[half].reshape(rows, width)
        value = tile[:, :kv_rank]
        # Of the lanes past kv_rank only those below key_width are key.
        rest = tile[:, kv_rank:]
        lane = kv_rank + jax.lax.broadcasted_iota(jnp.int32, rest.shape, 1)
        rest = jnp.where(lane < key_width, rest, jnp.zeros_like(rest))
        s = jax.lax.dot_general(q[:, :kv_rank], value, dims, preferred_element_type=jnp.float32, precision=prec)
        s = s + jax.lax.dot_general(q[:, kv_rank:], rest, dims, preferred_element_type=jnp.float32, precision=prec)
        s = s * sm_scale  # [heads, rows]
        col = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < length, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(value.dtype), value, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32, precision=prec
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    done_ref[0] = done + n_blocks
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def latent_decode_attention(
    q_lat: jax.Array,
    pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    key_width: int,
    kv_rank: int,
    sm_scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Absorbed decode attention of one query a slot over a paged latent pool.

    q_lat: [batch, heads, W], the pool's dtype, zero past ``key_width``.
    pool: [num_pages, page_size, W].  page_table: [batch, max_pages] int32.
    lengths: [batch] int32, the rows slot ``b`` sees (1 .. max_pages x
    page_size; an idle slot sees one row).  Returns ``o_lat`` [batch,
    heads, kv_rank] in the pool's dtype, in blocks of ``PAGES_PER_BLOCK``
    pages (at most the table's width).  ``interpret`` None: the compiled
    kernel on a TPU backend, the Pallas interpreter elsewhere."""
    batch, heads, width = q_lat.shape
    if pool.shape[2] != width or page_table.shape[0] != batch or lengths.shape != (batch,):
        raise ValueError(f"q_lat {q_lat.shape}, pool {pool.shape}, table {page_table.shape}, lengths {lengths.shape}")
    if not kv_rank <= key_width <= width:
        raise ValueError(f"kv_rank {kv_rank} <= key_width {key_width} <= width {width} must hold")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(
        q_lat, pool, page_table, lengths, key_width=key_width, kv_rank=kv_rank, sm_scale=sm_scale,
        ppb=min(PAGES_PER_BLOCK, page_table.shape[1]), interpret=interpret,
    )


# One jitted call: a program's attentions share ONE traced and lowered
# kernel (a decode step of longcat-flash-d4e16 has eight; lowered one by
# one they added about 12 s of a replica's set-up on the chip's host).
@functools.partial(jax.jit, static_argnames=("key_width", "kv_rank", "sm_scale", "ppb", "interpret"))
def _call(q_lat, pool, page_table, lengths, *, key_width, kv_rank, sm_scale, ppb, interpret):
    batch, heads, width = q_lat.shape
    _, page_size, _ = pool.shape
    max_pages = page_table.shape[1]
    kernel = functools.partial(
        _kernel, batch=batch, max_pages=max_pages, key_width=key_width, kv_rank=kv_rank, sm_scale=sm_scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda b, table, lens: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, kv_rank), lambda b, table, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, kv_rank), jnp.float32),
        ],
    )
    # The compiler's scheduler sees a custom call: tell it the size at
    # half the table live.
    live = batch * max_pages * page_size // 2
    itemsize = pool.dtype.itemsize
    return pl.pallas_call(
        kernel,
        name="latent_decode_attention",  # stable in a device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, kv_rank), pool.dtype),
        # The double buffer and its semaphores carry over from one slot's
        # program to the next.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * live * (key_width + kv_rank), transcendentals=heads * live,
            bytes_accessed=live * width * itemsize + batch * heads * (width + kv_rank) * itemsize,
        ),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32), q_lat, pool)
