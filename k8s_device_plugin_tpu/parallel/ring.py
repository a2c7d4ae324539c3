"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context workloads shard the SEQUENCE over devices (the ``sp`` axis) so
no chip ever holds the full [seq, seq] score matrix or even the full kv.
Each device keeps its resident q shard and passes its k/v shard around the
ICI ring with ``lax.ppermute``; at every step it folds the visiting kv block
into a running online-softmax state (same math as the Pallas flash kernel in
ops/flash_attention.py, lifted from "one VMEM tile at a time" to "one
device's shard at a time").  After ``sp`` steps every q row has attended to
every kv position, with peak per-device memory O(local_seq²) and traffic
that rides neighbor-to-neighbor ICI links — never a global all-gather.

The reference has no distributed compute at all (SURVEY.md §2.4: parallelism
is "the workload's problem"); this module is the workload-side answer, built
on XLA collectives rather than any NCCL/MPI pattern.

Layering: `ring_attention` is the per-device body (call inside `shard_map`);
`ring_self_attention` wraps it for a global [batch, heads, seq, head_dim]
array over a Mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = float("-inf")

def shard_map_unchecked(body, *, mesh, in_specs, out_specs):
    """`shard_map` with varying-manual-axes checking off — ulysses/pipeline/
    1F1B bodies all mix replicated inputs with per-device collectives,
    which the checker rejects."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def expand_gqa_kv(q, k, v):
    """Expand grouped-query k/v to q's full head count (the fallback when a
    sharding axis can't split kv_heads — ring and Ulysses wrappers share it)."""
    group = q.shape[1] // k.shape[1]
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _mark_varying(tree, axis_name):
    """Tag device-invariant values as varying over ``axis_name`` (shard_map
    tracks varying manual axes; scan carries must agree)."""
    return jax.lax.pcast(tree, axis_name, to="varying")


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    extra_varying: tuple = (),
) -> jax.Array:
    """Per-device ring attention body.

    Shapes are the LOCAL shards: [batch, heads, local_seq, head_dim], where
    global seq = local_seq * mesh.shape[axis_name] and shard i owns global
    positions [i*local_seq, (i+1)*local_seq).  Must run inside ``shard_map``
    (or ``pmap``) with ``axis_name`` bound.  ``extra_varying`` names any
    other manual axes the inputs are sharded over (dp/tp in a composed
    mesh), so the scan carry's varying-axis types line up.

    Grouped-query attention is native: k/v may carry ``kv_heads`` dividing
    q's ``heads``.  The rotating kv shard stays UN-expanded — ppermute
    traffic and kv memory scale with kv_heads, not heads (a group-factor
    ICI saving; q is reshaped to [b, kv_heads, group, seq, d] and the
    einsums contract against the shared kv head).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    batch, heads, seq_q, head_dim = q.shape
    kv_heads, seq_kv = k.shape[1], k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"q heads {heads} not a multiple of kv heads {kv_heads}")
    group = heads // kv_heads
    f32 = jnp.float32
    qf = q.astype(f32).reshape(batch, kv_heads, group, seq_q, head_dim)

    rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_kv), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_kv), 1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (rank - t) % n  # which shard's kv we hold at this step
        # h = kv head, g = member of its q-head group: kv has no g axis, so
        # one kv shard serves the whole group (GQA-native, no repeat).
        s = (
            jnp.einsum(
                "bhgqd,bhkd->bhgqk",
                qf,
                k_blk.astype(f32),
                preferred_element_type=f32,
            )
            * sm_scale
        )
        if causal:
            row_g = rank * seq_q + rows
            col_g = src * seq_kv + cols
            s = jnp.where(row_g >= col_g, s, NEG_INF)

        # Online softmax fold (identical update rule to the flash kernel).
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        seen = m_new > NEG_INF
        p = jnp.where(seen, jnp.exp(s - jnp.where(seen, m_new, 0.0)), 0.0)
        alpha = jnp.where(seen, jnp.exp(jnp.where(seen, m - m_new, 0.0)), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(f32), preferred_element_type=f32
        )

        # Rotate kv one hop around the ring (neighbor ICI traffic only).
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m_new, l_new, acc_new), None

    # The initial state is device-invariant; mark it as varying over every
    # manual axis the inputs vary over so the scan carry types line up
    # (shard_map tracks varying axes).
    m0, l0, acc0 = _mark_varying(
        (
            jnp.full((batch, kv_heads, group, seq_q, 1), NEG_INF, f32),
            jnp.zeros((batch, kv_heads, group, seq_q, 1), f32),
            jnp.zeros(qf.shape, f32),
        ),
        (axis_name,) + tuple(extra_varying),
    )
    (_, _, _, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n)
    )
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
    return (acc / l).astype(q.dtype).reshape(batch, heads, seq_q, head_dim)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
) -> jax.Array:
    """Global-view wrapper: [batch, heads, seq, head_dim] arrays, sequence
    sharded over ``mesh`` axis ``axis``; returns the same global shape.

    ``batch_axis``/``head_axis`` name mesh axes the batch/head dims are
    already sharded over (dp / tp in a composed mesh) so the engine keeps
    those dims sharded instead of all-gathering them at the shard_map
    boundary — the ring only ever communicates over ``axis``.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"seq {q.shape[2]} not divisible by {axis}={n}")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
        )
    if head_axis and k.shape[1] != q.shape[1]:
        tp_size = mesh.shape[head_axis]
        if k.shape[1] % tp_size:
            # GQA kv heads can't shard over the tp axis (e.g. 2 kv heads on
            # tp=4): expand to full heads here — the pre-GQA behavior —
            # rather than failing in device_put with an opaque error.  The
            # ring stays GQA-native whenever the sharding allows it.
            k, v = expand_gqa_kv(q, k, v)
    spec = P(batch_axis, head_axis, axis, None)
    body = functools.partial(
        ring_attention,
        axis_name=axis,
        causal=causal,
        sm_scale=sm_scale,
        extra_varying=tuple(a for a in (batch_axis, head_axis) if a),
    )
    shard_mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    sharding = NamedSharding(mesh, spec)
    return shard_mapped(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
