"""Linear attention by the gated delta rule with per-channel decay (Kimi
Delta Attention), the mixer of a model's linear-attention layers.

Per token ``t`` and head (``d`` channels, state ``S`` [d, d] keys x
values, float32, zero at a sequence's start)::

    q_t = l2norm(silu(conv_q(x W_q)));  k_t = l2norm(silu(conv_k(x W_k)));  v_t = silu(conv_v(x W_v))
    g_t = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)      the log-decay, one a channel
    beta_t = 2 sigmoid(x W_b)   (sigmoid alone without negative eigenvalues)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)
    y_t = concat_heads(norm(o_t) * w_o * sigmoid((x W_ga) W_gb)) W_o

``conv_*`` are causal depthwise convolutions of ``KdaConfig.conv`` taps
(one in-projection and one kernel over q | k | v here), ``norm`` an RMSNorm
over a head's channels with one weight shared by the heads.

One flax module with two computations over one set of ``cache`` variables:

- a multi-token call (a prefill chunk) runs the CHUNKWISE form
  (``kda_chunk``): sub-chunks of ``SUB_CHUNK`` tokens in turn, the
  state carried between them; inside a sub-chunk the delta rule's
  triangular system is solved by forward substitution (the WY form) and
  every decay is a pairwise difference of the cumulative log-decay, so no
  exponent is ever positive and nothing overflows in float32;
- a one-token call (a decode step) applies the update once
  (ops/kda_decode.py: the jnp step, or on a TPU the kernel that reads and
  writes each slot's state once, in place).

The state is one fixed-size block a sequence: the serving engine keeps it
in per-slot leaves, ``slot_kda`` [slots, H, d, d] float32 and
``slot_kda_conv`` [slots, conv - 1, 3 H d] (the inputs the convolutions
still need), which the compiled cache writers carry through graft, slot
clear and zero cache as they do a Mamba-2 mixer's (models/ssm.py).  A
layer of this kind holds NO pages.

Positions past a row's last real token (``last_positions``: a prompt
padded to its bucket) leave the state alone: there ``beta`` and ``g`` are
0 (decay 1, nothing written) and the convolution's tail is taken from the
last real inputs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.expert_ffn import on_kernel_lane
from ..ops.kda_decode import kda_decode, kda_step
from .ssm import _a_log_init, _inv_softplus_init, causal_conv

L2_EPS = 1e-6
# Sub-chunk of the chunkwise form: its pairwise decays are [C, C, d] a head,
# 537 MB at C = 16 over 64 rows x 64 heads (docs/serving.md).
SUB_CHUNK = 16


@dataclass(frozen=True)
class KdaConfig:
    """Shape of the linear-attention layers (a published config's
    ``linear_attn_config`` and ``kda_*`` keys)."""

    n_heads: int = 64
    head_dim: int = 128
    conv: int = 4  # short_conv_kernel_size
    rank: int = 128  # low rank of the decay and gate projections
    neg_eigval: bool = True  # beta in (0, 2), not (0, 1)

    @property
    def width(self) -> int:
        return self.n_heads * self.head_dim

    def __post_init__(self):
        if self.conv < 1:
            raise ValueError(f"conv {self.conv} must be >= 1")


def kernel_lane(q_len: int) -> bool:
    """Whether a decode call of ``q_len`` tokens a row runs the kernel: one
    token a row on a TPU backend.  A fact of the program, known before it
    is compiled."""
    return q_len == 1 and on_kernel_lane()


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_chunk(q, k, v, g, beta, s0, chunk: int):
    """The chunkwise form.  q (scaled), k, v, g [b, T, H, d] float32; beta
    [b, T, H]; s0 [b, H, d, d].  Returns (o [b, T, H, d], S_T).  Any T: the
    tail is padded with positions of beta = g = 0, which change nothing."""
    b, t_len, heads, d = q.shape
    hi = jax.lax.Precision.HIGHEST
    n = -(-t_len // chunk)
    pad = n * chunk - t_len

    def sub_chunks(x):  # [b, T, H, ...] -> [n, b, H, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, heads, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    incl = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def one(s, inp):
        qc, kc, vc, gc, bc = inp  # [b, H, C, d] x4, [b, H, C]
        cum = jnp.cumsum(gc, axis=2)  # <= 0, falling
        # exp(cum_t - cum_s) for s <= t, each exponent <= 0; 0 elsewhere.
        pair = jnp.exp(jnp.where(incl[:, :, None], cum[:, :, :, None] - cum[:, :, None], -jnp.inf))
        kk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", kc, kc, pair, precision=hi)
        qk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", qc, kc, pair, precision=hi)
        lower = jnp.where(strict, bc[..., None] * kk, 0.0)
        # w_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t): the system
        # (I + beta A) W = beta (V - K~ S0), unit lower triangular.
        rhs = bc[..., None] * (vc - jnp.einsum("bhtk,bhkv->bhtv", jnp.exp(cum) * kc, s, precision=hi))

        def row(t, w):
            lt = jax.lax.dynamic_index_in_dim(lower, t, 2, keepdims=False)  # [b, H, C]
            wt = jax.lax.dynamic_index_in_dim(rhs, t, 2, keepdims=False) - jnp.einsum(
                "bhs,bhsv->bhv", lt, w, precision=hi)
            return jax.lax.dynamic_update_index_in_dim(w, wt, t, 2)

        w = jax.lax.fori_loop(0, chunk, row, jnp.zeros_like(rhs))
        o = jnp.einsum("bhtk,bhkv->bhtv", jnp.exp(cum) * qc, s, precision=hi)
        o = o + jnp.einsum("bhts,bhsv->bhtv", jnp.where(incl, qk, 0.0), w, precision=hi)
        last = cum[:, :, -1:]  # [b, H, 1, d]
        s = s * jnp.exp(last)[:, :, 0, :, None] + jnp.einsum(
            "bhsk,bhsv->bhkv", jnp.exp(last - cum) * kc, w, precision=hi)
        return s, o

    f32 = jnp.float32
    s_t, o = jax.lax.scan(one, s0.astype(f32), tuple(sub_chunks(x.astype(f32)) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)  # [b, n, H, C, d]
    o = jnp.moveaxis(o, 2, 3).reshape(b, n * chunk, heads, d)
    return o[:, :t_len], s_t


class KdaMixer(nn.Module):
    """The linear attention of one layer; ``config`` is the model's
    ``GPTConfig`` (``config.kda`` a ``KdaConfig``).  With ``decode`` the
    state lives in the ``cache`` collection (``slot_kda``,
    ``slot_kda_conv``) and a call continues from it; without, a call is a
    whole sequence from a zero state."""

    config: Any
    decode: bool = False

    @nn.compact
    def __call__(self, hidden, positions, last_positions: Optional[jax.Array] = None):
        from .transformer import dense_site  # transformer imports this module

        cfg, kc = self.config, self.config.kda
        if cfg.quant is not None or cfg.lora_rank is not None or cfg.lora_serve:
            raise ValueError("linear attention (cfg.kda) is not supported with quant or LoRA")
        batch, t_len, _ = hidden.shape
        f32 = jnp.float32
        heads, d, width = kc.n_heads, kc.head_dim, kc.width

        with jax.named_scope("kda.project"):
            qkv = dense_site(cfg, 3 * width, name="in_proj")(hidden)
            decay_in = dense_site(cfg, width, name="f_b")(dense_site(cfg, kc.rank, name="f_a")(hidden))
            beta = jax.nn.sigmoid(dense_site(cfg, heads, name="b_proj")(hidden).astype(f32))
            if kc.neg_eigval:
                beta = 2.0 * beta
            gate = dense_site(cfg, width, name="g_b")(dense_site(cfg, kc.rank, name="g_a")(hidden))
        conv_w = self.param("conv_kernel", nn.initializers.normal(kc.conv ** -0.5), (kc.conv, 3 * width))
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _inv_softplus_init(1e-3, 1e-1), (width,))
        o_norm = self.param("o_norm", nn.initializers.ones, (d,))

        if self.decode:
            state = self.variable("cache", "slot_kda", jnp.zeros, (batch, heads, d, d), f32)
            conv = self.variable("cache", "slot_kda_conv", jnp.zeros, (batch, kc.conv - 1, 3 * width), cfg.dtype)
            s0, tail = state.value, conv.value
        else:
            s0 = jnp.zeros((batch, heads, d, d), f32)
            tail = jnp.zeros((batch, kc.conv - 1, 3 * width), cfg.dtype)

        valid = n_valid = None
        if last_positions is not None and t_len > 1:
            valid = positions <= last_positions[:, None]  # [batch, T]
            n_valid = jnp.clip(last_positions - positions[:, 0] + 1, 0, t_len)
        with jax.named_scope("kda.conv"):
            x, new_tail = causal_conv(qkv, tail, conv_w, jnp.zeros((), f32), n_valid)
            q, k, v = (y.reshape(batch, t_len, heads, d) for y in jnp.split(jax.nn.silu(x), 3, axis=-1))
            q, k = l2norm(q) * d ** -0.5, l2norm(k)
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            decay_in.astype(f32) + dt_bias.astype(f32)).reshape(batch, t_len, heads, d)
        if valid is not None:
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)

        if self.decode and t_len == 1:
            with jax.named_scope("kda.recur"):
                step = kda_decode if kernel_lane(1) else kda_step
                o, s_new = step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("kda.chunk"):
                o, s_new = kda_chunk(q, k, v, g, beta, s0, SUB_CHUNK)
        if self.decode:
            state.value, conv.value = s_new, new_tail.astype(cfg.dtype)

        with jax.named_scope("kda.out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps) * o_norm.astype(f32)
            o = o * jax.nn.sigmoid(gate.astype(f32)).reshape(batch, t_len, heads, d)
            return dense_site(cfg, cfg.hidden_size, name="out_proj")(o.reshape(batch, t_len, width).astype(cfg.dtype))
