"""Mamba-2 mixer: a selective state-space layer beside attention.

One flax module with two paths over one set of ``cache`` variables:

- the CHUNKED SCAN (``ssd_scan``) for a multi-token call, a prefill chunk
  that starts from the state the previous chunk left;
- the one-token RECURRENCE (``ssd_step``) for a decode step.

Both compute, per head (state ``h`` [P, N], one decay scalar a head)::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t + D * x_t

The state is NOT pages: it is one fixed-size block a sequence, so the
serving engine keeps it in per-slot leaves (``slot_ssm`` [slots, H, P, N]
float32, ``slot_conv`` [slots, K-1, conv_dim]: the K-1 inputs the causal
convolution still needs) beside the paged K/V pools, and its compiled cache
writers copy or zero a slot's row of every ``slot_*`` leaf
(models/engine_paging.py).

Precision: weights and activations in the model's dtype; the state, ``dt``,
the decays ``exp(dt * A)`` and the scan's cumulative sums in float32 (a
recurrence over hundreds of steps drifts in bfloat16).

Positions past a row's last real token (``last_positions``: a prompt padded
to its length bucket) must not reach the state: there ``dt`` is forced to 0
(decay 1, input 0) and the convolution's tail is taken from the last K-1
REAL inputs, which may lie in the previous chunk.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class MambaConfig:
    """Shape of the mixer (the ``mamba_*`` keys of a published config)."""

    d_ssm: int = 4096  # n_heads * head_dim
    n_heads: int = 32
    head_dim: int = 128
    d_state: int = 256
    n_groups: int = 2
    d_conv: int = 4
    chunk_size: int = 128
    # muP multipliers over the in-projection's segments z | x | B | C | dt.
    in_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_features(self) -> int:
        return self.d_ssm + self.conv_dim + self.n_heads

    def __post_init__(self):
        if self.d_ssm != self.n_heads * self.head_dim:
            raise ValueError(f"d_ssm {self.d_ssm} != {self.n_heads} heads x {self.head_dim}")
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_groups {self.n_groups} does not divide n_heads {self.n_heads}")
        if len(self.in_multipliers) != 5:
            raise ValueError("in_multipliers holds five values: z, x, B, C, dt")


def ssd_step(x, dt, a_neg, b, c, h):
    """One token of the recurrence.

    x [batch, H, P]; dt [batch, H] float32; a_neg [H] float32 (= -exp(A_log));
    b, c [batch, G, N]; h [batch, H, P, N] float32.
    Returns (y [batch, H, P] float32, h_new)."""
    batch, heads, p = x.shape
    groups = b.shape[1]
    rep = heads // groups  # head h reads group h // rep
    f32 = jnp.float32
    xg = (x.astype(f32) * dt[..., None]).reshape(batch, groups, rep, p)
    hg = h.reshape(batch, groups, rep, p, -1)
    dg = jnp.exp(dt * a_neg).reshape(batch, groups, rep)
    bg, cg = b.astype(f32), c.astype(f32)
    h_new = hg * dg[..., None, None] + xg[..., None] * bg[:, :, None, None, :]
    y = jnp.sum(h_new * cg[:, :, None, None, :], axis=-1)
    return y.reshape(batch, heads, p), h_new.reshape(h.shape)


def ssd_scan(x, dt, a_neg, b, c, h0, chunk: int):
    """The chunked scan (Mamba-2's state-space duality form): inside a chunk
    of ``chunk`` tokens the outputs are one masked, decay-weighted
    attention-like product; between chunks only the state is carried.

    x [batch, T, H, P]; dt [batch, T, H] float32, 0 where a position must
    leave the state alone; a_neg [H]; b, c [batch, T, G, N]; h0 [batch, H, P,
    N] float32.  Returns (y [batch, T, H, P] float32, h_T).  Any T: the tail
    chunk is padded with dt = 0 positions."""
    batch, t_len, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    n_chunks = -(-t_len // chunk)
    pad = n_chunks * chunk - t_len

    def chunks(v):
        v = jnp.pad(v.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        v = v.reshape(batch, n_chunks, chunk, *v.shape[2:])
        return jnp.moveaxis(v, 1, 0)  # [n_chunks, batch, chunk, ...]

    xs = chunks(x).reshape(n_chunks, batch, chunk, groups, rep, p)
    dts = chunks(dt).reshape(n_chunks, batch, chunk, groups, rep)
    bs, cs = chunks(b), chunks(c)
    a_g = a_neg.astype(f32).reshape(groups, rep)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(h, inp):
        xc, dtc, bc, cc = inp  # [b, L, G, R, P], [b, L, G, R], [b, L, G, N] x2
        cum = jnp.cumsum(dtc * a_g, axis=1)  # [b, L, G, R], <= 0, falling
        # Inside the chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t.B_s) x_s
        seg = cum[:, :, None] - cum[:, None, :]  # [b, t, s, G, R]
        seg = jnp.where(causal[None, :, :, None, None], seg, -jnp.inf)
        cb = jnp.einsum("btgn,bsgn->btsg", cc, bc, precision=hi)
        m = jnp.exp(seg) * cb[..., None] * dtc[:, None]  # [b, t, s, G, R]
        y = jnp.einsum("btsgr,bsgrp->btgrp", m, xc, precision=hi)
        # From the state the previous chunk left: y_t += exp(cum_t) C_t . h
        hg = h.reshape(batch, groups, rep, p, n)
        y += jnp.einsum("btgn,bgrpn->btgrp", cc, hg, precision=hi) * jnp.exp(cum)[..., None]
        # The state at the chunk's end.
        last = cum[:, -1]  # [b, G, R]
        w = jnp.exp(last[:, None] - cum) * dtc  # [b, L, G, R]
        h_new = hg * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bsgrp,bsgn->bgrpn", xc * w[..., None], bc, precision=hi
        )
        return h_new.reshape(h.shape), y

    h_t, ys = jax.lax.scan(one_chunk, h0.astype(f32), (xs, dts, bs, cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(batch, n_chunks * chunk, heads, p)
    return y[:, :t_len], h_t


def causal_conv(xbc, tail, weight, bias, n_valid=None):
    """Causal depthwise convolution over time with the K-1 inputs before
    the first position given (``tail`` [batch, K-1, C]; zeros at a
    sequence's start).  xbc [batch, T, C]; weight [K, C] (row K-1 multiplies
    the current position); bias [C].

    Returns (out [batch, T, C] float32, new tail [batch, K-1, C]): the
    last K-1 inputs, or with ``n_valid`` [batch] (real positions of this
    call, 0..T) the K-1 inputs that end at each row's last REAL position."""
    k = weight.shape[0]
    t_len = xbc.shape[1]
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)  # [b, K-1+T, C]
    w = weight.astype(jnp.float32)
    out = sum(ext[:, i : i + t_len].astype(jnp.float32) * w[i] for i in range(k))
    out = out + bias.astype(jnp.float32)
    if n_valid is None:
        new_tail = ext[:, t_len:]
    else:
        idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]  # [b, K-1]
        new_tail = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
    return out, new_tail


def _inv_softplus_init(lo: float, hi: float):
    """dt_bias as Mamba-2 initialises it: the inverse softplus of a
    log-uniform step in [lo, hi]."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The mixer of one block: in-projection, causal convolution, the
    selective state-space recurrence, gated group RMSNorm, out-projection.

    ``config`` is the model's ``GPTConfig`` (``config.mixer`` a
    ``MambaConfig``).  With ``decode`` the state lives in the ``cache``
    collection (``slot_ssm``, ``slot_conv``) and a call continues from it:
    one token through ``ssd_step``, several through ``ssd_scan``.  Without,
    a call is a whole sequence from a zero state."""

    config: Any
    decode: bool = False

    @nn.compact
    def __call__(self, hidden, positions, last_positions: Optional[jax.Array] = None):
        from .transformer import dense_site  # transformer imports this module

        cfg, mc = self.config, self.config.mixer
        if cfg.lora_rank is not None or cfg.lora_serve:
            raise ValueError("LoRA adapters are not supported on a model with a state-space mixer")
        batch, t_len, _ = hidden.shape
        f32 = jnp.float32
        heads, p, groups, n = mc.n_heads, mc.head_dim, mc.n_groups, mc.d_state
        gn = groups * n

        zxbcdt = dense_site(cfg, mc.in_features, name="in_proj")(hidden)
        if any(m != 1.0 for m in mc.in_multipliers):
            sizes = (mc.d_ssm, mc.d_ssm, gn, gn, heads)
            mup = jnp.concatenate([jnp.full((s,), m, f32) for s, m in zip(sizes, mc.in_multipliers)])
            zxbcdt = zxbcdt * mup.astype(zxbcdt.dtype)
        z, xbc, dt = jnp.split(zxbcdt, [mc.d_ssm, mc.d_ssm + mc.conv_dim], axis=-1)

        conv_w = self.param("conv_kernel", nn.initializers.normal(mc.d_conv ** -0.5), (mc.d_conv, mc.conv_dim))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (mc.conv_dim,))
        dt_bias = self.param("dt_bias", _inv_softplus_init(1e-3, 1e-1), (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        d_skip = self.param("D", nn.initializers.ones, (heads,))
        norm_w = self.param("norm_scale", nn.initializers.ones, (mc.d_ssm,))

        if self.decode:
            ssm = self.variable("cache", "slot_ssm", jnp.zeros, (batch, heads, p, n), f32)
            conv = self.variable("cache", "slot_conv", jnp.zeros, (batch, mc.d_conv - 1, mc.conv_dim), cfg.dtype)
            h0, tail = ssm.value, conv.value
        else:
            h0 = jnp.zeros((batch, heads, p, n), f32)
            tail = jnp.zeros((batch, mc.d_conv - 1, mc.conv_dim), cfg.dtype)

        valid = n_valid = None
        if last_positions is not None and t_len > 1:
            valid = positions <= last_positions[:, None]  # [batch, T]
            n_valid = jnp.clip(last_positions - positions[:, 0] + 1, 0, t_len)
        xbc, new_tail = causal_conv(xbc, tail, conv_w, conv_b, n_valid)
        xbc = nn.silu(xbc).astype(cfg.dtype)
        xs, b, c = jnp.split(xbc, [mc.d_ssm, mc.d_ssm + gn], axis=-1)
        xs = xs.reshape(batch, t_len, heads, p)
        b = b.reshape(batch, t_len, groups, n)
        c = c.reshape(batch, t_len, groups, n)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))  # [batch, T, H]
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0.0)
        a_neg = -jnp.exp(a_log.astype(f32))

        if self.decode and t_len == 1:
            with jax.named_scope("mixer.step"):
                y, h_new = ssd_step(xs[:, 0], dt[:, 0], a_neg, b[:, 0], c[:, 0], h0)
                y = y[:, None]
        else:
            with jax.named_scope("mixer.scan"):
                y, h_new = ssd_scan(xs, dt, a_neg, b, c, h0, mc.chunk_size)
        if self.decode:
            ssm.value, conv.value = h_new, new_tail.astype(cfg.dtype)

        y = y + d_skip.astype(f32)[:, None] * xs.astype(f32)
        # Gate, then RMSNorm over each group's channels (norm after the gate).
        y = y.reshape(batch, t_len, mc.d_ssm) * nn.silu(z.astype(f32))
        yg = y.reshape(batch, t_len, groups, mc.d_ssm // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = (yg.reshape(batch, t_len, mc.d_ssm) * norm_w.astype(f32)).astype(cfg.dtype)
        return dense_site(cfg, cfg.hidden_size, name="out_proj")(y)
