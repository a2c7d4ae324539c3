"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second classic long-context layout (alongside ring attention, ring.py):
instead of streaming kv shards around the ring, ONE all-to-all per tensor
re-partitions [batch, heads, seq/n, head_dim] into [batch, heads/n, seq,
head_dim] — every device then holds the FULL sequence for a SUBSET of heads,
runs an ordinary (flash) attention locally with no inner-loop communication,
and a reverse all-to-all restores sequence sharding.  Traffic is O(seq·d)
per device in two bursts that XLA lowers to ICI all-to-alls, versus ring's
n neighbor hops overlapped with compute; Ulysses wins when heads ≥ n and the
all-to-all fits comfortably in ICI bisection bandwidth, ring wins for very
long sequences or few heads.  (Pattern from the DeepSpeed-Ulysses paper;
built here on jax.lax.all_to_all inside shard_map — the reference has no
distributed compute at all, SURVEY.md §2.4.)

Layering mirrors ring.py: `ulysses_attention` is the per-device body (call
inside shard_map with the axis bound); `ulysses_self_attention` wraps a
global array view over a Mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.flash_attention import flash_attention, mha_reference
from .ring import expand_gqa_kv, shard_map_unchecked


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Per-device Ulysses body.

    Local shard shapes [batch, heads, local_seq, head_dim]; global seq =
    local_seq * n where n = size of ``axis_name``; heads must divide by n.
    Must run inside shard_map (or pmap) with ``axis_name`` bound.

    Grouped-query attention: when ``kv_heads %% n == 0`` the kv tensors ride
    their own (group-times smaller) all-to-all and the local attention runs
    GQA-natively through the flash kernel; otherwise kv is expanded to full
    heads first (the pre-GQA behavior).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)  # concrete under shard_map
    if q.shape[1] % n:
        raise ValueError(
            f"heads {q.shape[1]} not divisible by {axis_name}={n}; "
            "use ring attention for head-poor long-context models"
        )
    kv_heads = k.shape[1]
    if q.shape[1] % kv_heads:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads {kv_heads}"
        )
    if kv_heads != q.shape[1] and kv_heads % n:
        # Too few kv heads to scatter over the axis: expand to full heads
        # (the attention itself would handle GQA; the all-to-all cannot).
        k, v = expand_gqa_kv(q, k, v)

    def scatter_heads(x):
        # [b, h, s/n, d] -> [b, h/n, s, d]: each device trades head blocks
        # for sequence blocks with every ring peer in one all-to-all.
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def gather_heads(x):
        # [b, h/n, s, d] -> [b, h, s/n, d]: the inverse exchange.
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    q_full = scatter_heads(q)
    k_full = scatter_heads(k)
    v_full = scatter_heads(v)
    # Full-sequence attention on the owned heads.  128-tileable sequences go
    # through the O(seq)-memory flash kernel (ops/flash_attention.py) — no
    # [seq, seq] score matrix is ever materialized; anything else falls back
    # to the plain-XLA oracle (same policy as models/transformer.py) instead
    # of failing deep inside Pallas block validation.
    seq_full = q_full.shape[2]
    block = min(128, seq_full)
    if seq_full % block == 0:
        out_full = flash_attention(
            q_full, k_full, v_full, causal=causal, sm_scale=sm_scale
        )
    else:
        out_full = mha_reference(
            q_full, k_full, v_full, causal=causal, sm_scale=sm_scale
        )
    return gather_heads(out_full)


def ulysses_self_attention(
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
    Requires local heads % mesh.shape[axis] == 0 (the head-scatter step).

    ``batch_axis``/``head_axis`` name mesh axes the batch/head dims are
    already sharded over (dp / tp in a composed mesh) so those dims stay
    sharded through the exchange instead of being all-gathered at the
    shard_map boundary; with ``head_axis`` set, the heads each device
    scatters are its local (tp-sharded) head group.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"seq {q.shape[2]} not divisible by {axis}={n}")
    local_heads = q.shape[1] // (mesh.shape[head_axis] if head_axis else 1)
    if local_heads % n:
        raise ValueError(
            f"local heads {local_heads} not divisible by {axis}={n}; "
            "use ring attention for head-poor long-context models"
        )
    if head_axis and k.shape[1] != q.shape[1] and k.shape[1] % mesh.shape[head_axis]:
        # GQA kv heads can't shard over the tp axis: expand before placing
        # (same fallback as ring_self_attention) instead of an opaque
        # device_put failure.
        k, v = expand_gqa_kv(q, k, v)
    spec = P(batch_axis, head_axis, axis, None)
    body = functools.partial(
        ulysses_attention, axis_name=axis, causal=causal, sm_scale=sm_scale
    )
    # The Pallas call inside the body reports no varying-manual-axes info on
    # its outputs, so shard_map's vma checking must be off.
    shard_mapped = shard_map_unchecked(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    sharding = NamedSharding(mesh, spec)
    return shard_mapped(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
