"""The few-token expert layer as one grouped FFN over the stacked weights:
a touched expert's weights are read once, in place.

For rows ``x`` [r, h] and the held experts' stacks ``gate``/``up``
[held, h, f] and ``down`` [held, f, h]::

    out = sum over held e with counts[e] > 0 of
          (silu(x . gate_e) * (x . up_e)).astype(x.dtype) . down_e * weight_of[e][:, None]

Every touched expert runs over EVERY row (``weight_of`` is 0 where a row
did not choose it): the few-token regime of models/moe.py, where an expert
sees a handful of the rows and the layer is bound by the bytes of the
weights it reads, not by FLOPs.

**The XLA lane** (``_experts_xla``) is a ``fori_loop`` over the held experts
with a real branch on each one's count: ``dynamic_index_in_dim`` takes the
expert's slice out of each stack inside the branch that runs it, and the
compiler fuses that slice into the matmul that reads it (no copy; three
fusions an expert, 75-79 % of a v5e's bandwidth at 64 rows).  It is the
serving path off the TPU and the oracle of the parity tests.

**The kernel** chooses its own HBM blocks.  The grid is ``(held, f /
tile_f)``, both sequential.  Two scalars are prefetched: ``order`` (the
local indices of the touched experts first, ascending, as the loop visits
them, then the last touched index repeated) and ``n_touched``.  The weight
``BlockSpec``s index the STACKS: gate and up ``(None, h, tile_f)`` at
``(order[g], 0, j)``, down ``(None, tile_f, h)`` at ``(order[g], j, 0)``,
so the pipeline DMAs tile ``j`` of expert ``order[g]`` from HBM straight
into VMEM, the next tile in flight while this one is multiplied (88 % of
the bandwidth there: docs/kernels.md).  A grid step with ``g >=
n_touched`` names the block of the step before it (the last touched
expert's last tile), so the pipeline fetches nothing, and ``pl.when``
skips its arithmetic: an untouched expert's weights are not read.  With no
real token at all (``n_touched`` 0: warm-up, an idle replica) the whole
grid names one tile of expert ``order[0]``: ``tile_f / f`` of one expert is
read and nothing computed.  ``x`` and the float32 output keep one block
index over the whole grid: resident in VMEM, written back once.

Precision is the XLA lane's: the operands in ``x.dtype``, float32
accumulation on all three matmuls, the activation cast to ``x.dtype``
before the down projection, float32 out.  The f axis is summed tile by
tile in float32 where the XLA lane sums it inside one matmul: the two lanes
differ by float32 rounding of that sum.

``tile_f`` and the VMEM limit are a row of ops/tuning.py
(``EXPERT_FFN_ROWS``, by ``device_kind``).  Lanes as ops/paged_attention.py:
``use_pallas=None`` sends a TPU backend to the compiled kernel and every
other backend to the XLA lane; ``interpret=True`` forces the kernel through
the Pallas interpreter (tests/test_expert_ffn.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning

# Rows are padded to the bfloat16 sublane tile for the compiled kernel.
_ROW_TILE = 16


def on_kernel_lane() -> bool:
    """Whether ``expert_ffn`` left to itself runs the compiled kernel: a
    fact of the backend, so a caller that compiles a program knows it
    before the program exists."""
    return jax.default_backend() == "tpu"


def expert_slice_ffn(rows, e, w_gate, w_up, w_down):
    """Held expert ``e`` (traced) over rows [r, h]; float32 out.  The
    expert's slice of the stacked weights is taken HERE, inside the branch
    that runs it: a branch not taken reads nothing."""
    f32 = jnp.float32
    gate_w, up_w, down_w = (jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False) for w in (w_gate, w_up, w_down))
    gate = jnp.dot(rows, gate_w, preferred_element_type=f32)
    up = jnp.dot(rows, up_w, preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(rows.dtype), down_w, preferred_element_type=f32)


def visit_order(counts):
    """``(order [held] int32, n_touched int32)``: the touched experts'
    local indices ascending, then the last touched one repeated (0 where
    none is touched)."""
    held = counts.shape[0]
    touched = counts > 0
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    (first,) = jnp.nonzero(touched, size=held, fill_value=0)
    last = first[jnp.maximum(n_touched - 1, 0)]
    order = jnp.where(jnp.arange(held) < n_touched, first, last)
    return order.astype(jnp.int32), n_touched


def _experts_xla(rows, weight_of, counts, w_gate, w_up, w_down):
    n_held = counts.shape[0]

    def skip(e, out):
        return out, jnp.zeros((), jnp.int32)

    def full(e, out):
        return out + expert_slice_ffn(rows, e, w_gate, w_up, w_down) * weight_of[e][:, None], counts[e]

    def one_expert(e, carry):
        out, computed = carry
        out, done = jax.lax.switch((counts[e] > 0).astype(jnp.int32), (skip, full), e, out)
        return out, computed + done

    return jax.lax.fori_loop(
        0, n_held, one_expert, (jnp.zeros(rows.shape, jnp.float32), jnp.zeros((), jnp.int32))
    )


def _kernel(order_ref, n_ref, x_ref, wt_ref, gate_ref, up_ref, down_ref, out_ref):
    g, j = pl.program_id(0), pl.program_id(1)
    # float32 operands (the CPU tests) need HIGHEST or a matmul costs ~2e-3.
    prec = jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32 else None

    @pl.when((g == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(g < n_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32, precision=prec)
        up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32, precision=prec)
        act = (jax.nn.silu(gate) * up).astype(x.dtype)
        part = jnp.dot(act, down_ref[...], preferred_element_type=jnp.float32, precision=prec)
        out_ref[...] += part * wt_ref[...]


def _experts_pallas(rows, weight_of, order, n_touched, w_gate, w_up, w_down, *, tile_f, vmem_limit_bytes, interpret):
    r, h = rows.shape
    held, _, f = w_gate.shape
    nf = f // tile_f

    def tile(g, j, n):
        # Past the touched experts: the block of the step before, no fetch.
        return jnp.where(g < n[0], j, nf - 1)

    resident = lambda g, j, order, n: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(held, nf),
        in_specs=[
            pl.BlockSpec((r, h), resident),
            pl.BlockSpec((None, r, 1), lambda g, j, order, n: (order[g], 0, 0)),
            pl.BlockSpec((None, h, tile_f), lambda g, j, order, n: (order[g], 0, tile(g, j, n))),
            pl.BlockSpec((None, h, tile_f), lambda g, j, order, n: (order[g], 0, tile(g, j, n))),
            pl.BlockSpec((None, tile_f, h), lambda g, j, order, n: (order[g], tile(g, j, n), 0)),
        ],
        out_specs=pl.BlockSpec((r, h), resident),
    )
    width = rows.dtype.itemsize
    return pl.pallas_call(
        _kernel,
        name="expert_ffn",  # stable in a device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h), jnp.float32),
        # Both axes carry the resident output block.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=vmem_limit_bytes
        ),
        # The compiler's scheduler sees a custom call: tell it the size at
        # half the held experts touched.
        cost_estimate=pl.CostEstimate(
            flops=3 * r * h * f * held, transcendentals=r * f * held // 2,
            bytes_accessed=3 * h * f * width * held // 2 + r * h * (width + 4),
        ),
        interpret=interpret,
    )(order, n_touched.reshape(1), rows, weight_of[:, :, None], w_gate, w_up, w_down)


def expert_ffn(
    rows: jax.Array,
    weight_of: jax.Array,
    counts: jax.Array,
    experts_gate: jax.Array,
    experts_up: jax.Array,
    experts_down: jax.Array,
    *,
    tile_f: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Every touched held expert over every row, weighted and summed.

    rows: [r, h], the layer's compute dtype.  weight_of: [held, r] float32,
    a row's weight for each held expert (0 where not chosen).  counts:
    [held] int32, rows that chose each held expert; an expert with count 0
    is not run and its weights are not read.  experts_gate / experts_up:
    [held, h, f], experts_down: [held, f, h], in rows' dtype.

    Returns ``(out [r, h] float32, computed int32)``: ``computed`` is the
    sum of ``counts`` over the experts that were run, which the caller
    holds against the assignments it made (models/moe.py ``dropped``).

    ``tile_f``: the kernel's tile of the f axis (None = the generation's
    row in ops/tuning.py; it must divide f, else the whole of f is one
    tile).  ``use_pallas`` / ``interpret``: None routes a TPU backend to
    the compiled kernel and everything else to the XLA lane;
    ``interpret=True`` forces the kernel through the Pallas interpreter.
    """
    (r, h), (held, _, f) = rows.shape, experts_gate.shape
    if weight_of.shape != (held, r) or counts.shape != (held,):
        raise ValueError(f"weight_of {weight_of.shape} and counts {counts.shape} for {held} experts over {r} rows")
    if experts_up.shape != (held, h, f) or experts_down.shape != (held, f, h) or experts_gate.shape[1] != h:
        raise ValueError(
            f"stacks gate {experts_gate.shape} up {experts_up.shape} down {experts_down.shape} for rows {rows.shape}"
        )
    on_tpu = on_kernel_lane()
    if use_pallas is None:
        use_pallas = on_tpu or bool(interpret)
    if interpret is None:
        interpret = not on_tpu
    if not use_pallas:
        return _experts_xla(rows, weight_of, counts, experts_gate, experts_up, experts_down)
    row, _ = tuning.expert_ffn_row()
    tile_f = row.tile_f if tile_f is None else tile_f
    if f % tile_f:
        tile_f = f
    order, n_touched = visit_order(counts)
    pad = -r % _ROW_TILE
    if pad:
        rows, weight_of = jnp.pad(rows, ((0, pad), (0, 0))), jnp.pad(weight_of, ((0, 0), (0, pad)))
    out = _experts_pallas(
        rows, weight_of, order, n_touched, experts_gate, experts_up, experts_down,
        tile_f=tile_f, vmem_limit_bytes=row.vmem_limit_bytes, interpret=interpret,
    )
    computed = jnp.sum(jnp.where(jnp.arange(held) < n_touched, counts[order], 0), dtype=jnp.int32)
    return out[:r], computed
