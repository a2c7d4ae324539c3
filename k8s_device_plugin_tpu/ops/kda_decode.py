"""One decode step of the gated delta rule with per-channel decay, every
slot's matrix state read and written once, in place.

For one slot and head, with the state ``S`` [d_k, d_v] float32 (keys x
values), the decay ``a = exp(g)`` [d_k], the write strength ``beta`` and the
token's ``q`` (already scaled by 1/sqrt(d_k)), ``k`` (l2-normed) and ``v``::

    S~ = Diag(a) S
    u  = S~^T k                      what the state recalls for k
    S' = S~ + (beta k) (v - u)^T     = (I - beta k k^T) Diag(a) S + beta k v^T
    o  = S'^T q

**The jnp lane** (``kda_step``) is those four lines as einsums: the
compiler reads the state for ``u`` and again for ``S'`` and ``o``.  It is
the step off the TPU and the oracle of the parity tests.

**The kernel** has a grid of (slot, head block): a block is
``HEADS_PER_BLOCK`` heads of one slot, their states DMA'd from HBM into
VMEM once, computed, and written back over themselves
(``input_output_aliases``: the state is the donated cache leaf, no second
copy of it exists).  Inside a block the heads are taken ``GROUP`` at a
time, so the temporaries stay a group's.  A head's vectors come in as the
rows of one [8, d] tile (``a``, ``k``, ``beta k``, ``q``, ``v``, zeros),
laid out in HBM as they are in VMEM; the kernel turns the tile to get the
per-key vectors as columns (keys on sublanes), each multiplies the
state's rows by a lane broadcast, and ``u`` and ``o`` are sums over
sublanes.  ``o`` goes out [slots, heads, d_v].  The state's width and the
keys' are one ``d`` (the keys and the values of a head are as wide).

Numerics are the jnp lane's in float32: the two lanes differ by the
order of float32 sums (tests/test_kda.py).

``interpret`` None: the compiled kernel on a TPU backend, the Pallas
interpreter elsewhere.  The caller chooses the lane (models/kda.py
``kernel_lane``: a TPU backend, one query a row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a grid step: 16 heads of 128 x 128 float32 are 1 MiB of state in
# and 1 MiB out, double-buffered, beside the columns' 1 MiB.
HEADS_PER_BLOCK = 16
# Heads computed at once inside a block.
GROUP = 4


def kda_step(state, q, k, v, g, beta):
    """The jnp lane.  state [b, H, d_k, d_v] float32; q (scaled), k, v, g
    [b, H, d] float32; beta [b, H] float32.  Returns (o [b, H, d_v], S')."""
    hi = jax.lax.Precision.HIGHEST
    s = state * jnp.exp(g)[..., None]
    u = jnp.einsum("bhk,bhkv->bhv", k, s, precision=hi)
    s = s + (beta[..., None] * k)[..., None] * (v - u)[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=hi), s


def _kernel(state_ref, rows_ref, state_out, o_ref):
    heads = state_ref.shape[1]
    for lo in range(0, heads, GROUP):
        hs = slice(lo, min(lo + GROUP, heads))
        x = rows_ref[0, hs]  # [G, 8, d]: a, k, beta k, q, v, 0, 0, 0
        a, k, kb, q = (c[..., None] for c in jnp.unstack(jnp.swapaxes(x, 1, 2)[..., :4], axis=-1))
        s = state_ref[0, hs] * a
        u = jnp.sum(s * k, axis=1, keepdims=True)  # [G, 1, d]
        s = s + kb * (x[:, 4:5] - u)
        state_out[0, hs] = s
        o_ref[0, hs] = jnp.sum(s * q, axis=1)


def kda_decode(state, q, k, v, g, beta, *, interpret: bool | None = None):
    """``kda_step`` through the kernel: the same arguments and results."""
    b, heads, d, d_v = state.shape
    if d_v != d or state.dtype != jnp.float32 or beta.shape != (b, heads):
        raise ValueError(f"state {state.shape} {state.dtype}, beta {beta.shape}")
    if any(x.shape != (b, heads, d) for x in (q, k, v, g)):
        raise ValueError(f"state {state.shape}: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    zero = jnp.zeros_like(q)
    rows = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q, v, zero, zero, zero], axis=2).astype(jnp.float32)
    return _call(state, rows, hpb=min(HEADS_PER_BLOCK, heads), interpret=interpret)


# One jitted call: a program's linear-attention layers share ONE traced
# and lowered kernel (models/kda.py; lowered one by one, as the latent
# kernel's were, each adds seconds of a replica's set-up).
@functools.partial(jax.jit, static_argnames=("hpb", "interpret"))
def _call(state, rows, *, hpb, interpret):
    b, heads, d, _ = state.shape
    if heads % hpb:
        hpb = heads
    block = lambda *tail: pl.BlockSpec((1, hpb, *tail), lambda i, j: (i, j) + (0,) * len(tail))  # noqa: E731
    new, o = pl.pallas_call(
        _kernel,
        name="kda_decode",  # stable in a device trace
        grid=(b, heads // hpb),
        in_specs=[block(d, d), block(8, d)],
        out_specs=[block(d, d), block(d)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32), jax.ShapeDtypeStruct((b, heads, d), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * state.size, transcendentals=0, bytes_accessed=(2 * state.size + rows.size + b * heads * d) * 4,
        ),
        interpret=interpret,
    )(state, rows)
    return o, new
