"""The serving expert layer: a float32 router over the PUBLISHED number of
experts, a selection bias, top-k, zero-computation (identity) experts, and
this chip's share of the routed experts, dropless with static shapes.

For a token ``u`` (``E`` routed experts, ``Z`` identity experts, ``k``
chosen, scaling ``s``)::

    p = softmax(float32(u) . float32(W_r))              E + Z wide
    chosen = the k largest of p + b                     b biases the CHOICE only
    w_i = s * p_i for i chosen                          not renormalised
    M(u) = sum_{i < E chosen} w_i down_i(silu(gate_i u) * up_i u) + sum_{i >= E chosen} w_i u

The same layer serves a plain pre-norm block's feed-forward (Solar Open
2's: ``MoeConfig.score`` "sigmoid", ``s = sigmoid(float32(u) . float32(W_r))``
each on its own, the chosen weights renormalised to sum to 1 with
``norm_topk``, no identity experts, and a shared SwiGLU expert of
``shared_size`` computed for every token, ``moe.shared``).  A config that
sets none of these fields traces the operations it traced before.

**The chip's share.**  The layer is told which routed experts it HOLDS
(``MoeConfig.held``: with 512 experts over 32 chips, expert-parallel rank 0
holds 0..15).  It routes over all ``E + Z``, computes the chosen held
experts' part and every chosen identity expert's (those cost no weights and
are computed where the token is), and leaves out what the absent experts
would add: that partial ``M`` is what goes on.  On one chip the layer runs
without the exchange that would bring other chips' tokens here and take
these tokens there; nothing stands in for it.

**Dropless, static shapes** (``moe.experts``).  No capacity, no dropped
token; what runs depends on how many tokens came, which the code reads off
its input's shape:

- FEW tokens (``few_tokens``: up to 256: every decode block and single
  step, a one-prompt chunk of 256): an expert runs over all of them or not
  at all, and the layer is ONE call of ``ops/expert_ffn.py``.  On a TPU
  that is a Pallas kernel whose grid walks the touched experts: a block of
  expert ``e`` is DMA'd from the stack straight into VMEM by an index map
  that reads ``e`` from a prefetched scalar, an untouched expert's weights
  are not read.  A decode step of 64 tokens touches 6 to 10 of 16 held
  experts, and a decode step is bound by the bytes it reads.  Elsewhere it
  is the loop below with two branches (none / every token).
- MANY tokens (chunks of two prompts and more): each held expert in turn
  (one ``fori_loop`` body: unrolled, a 64 x 256 chunk's program took three
  times as long to compile, 29 such programs a warm-up) runs ONE branch on
  its token count ``n`` (``lax.switch``, a real branch on the chip).
  ``n == 0``: nothing; the expert's weights are not read.  ``n <= C``
  (``gather_rows``, an eighth of the tokens, never fewer than 256): its
  tokens are gathered into ``C`` rows, run, and scattered back weighted: 2
  x 3 x h x f FLOPs a row, so a 64 x 256 prefill chunk costs 16 x 2,048
  rows where every expert over every token would cost 16 x 16,384 (as much
  again as the rest of the chunk).  ``n > C``: the expert runs over EVERY
  token under its weights (0 where it was not chosen).  Any routing gives
  the reference's numbers, all of a chunk's tokens on one expert included;
  the cost is only paid then.  There the layer is bound by FLOPs and the
  compiler's matmuls stay.

What it costs, read on the chip (PERF.md Findings, PR 33).  The loop does
NOT copy a touched expert's slice: the ``dynamic-slice`` is fused into the
matmul that reads it, three fusions an expert at 37-40 us each (645 GB/s,
79 % of the chip's 819), 0.978 ms a layer alone at 64 rows and 8 of 16
touched.  The kernel takes 0.842 ms there (717 GB/s) and 0.90 ms where the
loop takes 1.30 at 256 rows.  Every held expert over every token as three
matmuls over the whole stack (no loop, no branch, all sixteen read once)
was slower than the loop and held 1.8 GB more (1,654-1,750 tokens/s
against 1,853-1,907, PR 32).  The many-token branches are untouched: a
gather, three matmuls and a scatter-add an expert (ROADMAP Reach A2).

**Counts** (``moe.route``).  Each call sows one int32 vector into the
``moe_stats`` collection (``STATS``: assignments to held, identity and
absent experts, assignments not computed, which must read 0, held experts
touched, whether any real token came, then a count per held expert); the
serving engine carries the sum through a decode block and reads it with the
block's tokens.  ``token_mask`` marks the real tokens: an idle slot's or a
padded position's row routes nowhere, touches no expert and counts nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.expert_ffn import expert_ffn, expert_slice_ffn

# Leading entries of the sown vector; a count per held expert follows.
STATS = ("held", "identity", "absent", "dropped", "touched", "active")


@dataclass(frozen=True)
class MoeConfig:
    """The expert layer of a shortcut-connected double-layer (the topology
    is ``models/transformer.py`` ``ShortcutBlock``'s) or of a plain
    pre-norm block.  ``n_routed`` and ``n_zero`` are the PUBLISHED counts:
    the router is ``n_routed + n_zero`` wide whatever ``held`` says."""

    n_routed: int = 512
    n_zero: int = 256
    top_k: int = 12
    scaling: float = 6.0
    expert_size: int = 2048
    held: tuple[int, ...] = tuple(range(16))
    # A shared SwiGLU expert of this width, computed for every token where
    # the token is (0 = none).
    shared_size: int = 0
    # The router's scores: "softmax" over all experts, or "sigmoid" each.
    score: str = "softmax"
    # Chosen weights renormalised to sum to 1 (before ``scaling``).
    norm_topk: bool = False

    def __post_init__(self):
        if not self.held or len(set(self.held)) != len(self.held):
            raise ValueError(f"held must name distinct experts, got {self.held}")
        if min(self.held) < 0 or max(self.held) >= self.n_routed:
            raise ValueError(f"held experts {self.held} are not among the {self.n_routed} routed")
        if not 1 <= self.top_k <= self.n_routed + self.n_zero:
            raise ValueError(f"top_k {self.top_k} of {self.n_routed + self.n_zero} experts")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score must be softmax|sigmoid, got {self.score!r}")

    @property
    def width(self) -> int:
        return self.n_routed + self.n_zero

    @property
    def stats_width(self) -> int:
        return len(STATS) + len(self.held)


def few_tokens(n_tokens: int) -> bool:
    """Whether ``n_tokens`` are few: the gathered branch would hold them
    all, so an expert runs over every token or not at all, one call of
    ops/expert_ffn.py (every decode step of up to 256 slots, a one-prompt
    chunk of 256)."""
    return gather_rows(n_tokens) >= n_tokens


def gather_rows(n_tokens: int) -> int:
    """Rows of the gathered branch: every token up to 256, then an eighth
    of them, never fewer than 256 (under uniform routing a held expert's
    mean share is top_k / (E + Z) of the tokens: a 64th at 12 of 768)."""
    return n_tokens if n_tokens <= 256 else max(256, n_tokens // 8)


def _many_token_experts(u, weight_of, counts, w_gate, w_up, w_down):
    """``moe.experts`` over many tokens [n, h] (chunks of two prompts and
    more): each held expert in turn runs one of three branches on its
    count.  Returns (out [n, h] float32, assignments computed)."""
    n, cap = u.shape[0], gather_rows(u.shape[0])

    def ffn(rows, e):
        return expert_slice_ffn(rows, e, w_gate, w_up, w_down)

    def skip(e, out):
        return out, jnp.zeros((), jnp.int32)

    def full(e, out):
        return out + ffn(u, e) * weight_of[e][:, None], counts[e]

    def gathered(e, out):
        (idx,) = jnp.nonzero(weight_of[e] != 0, size=cap, fill_value=n)
        rows = jnp.take(u, idx, axis=0, mode="fill", fill_value=0)
        wt = jnp.take(weight_of[e], idx, mode="fill", fill_value=0.0)
        out = out.at[idx].add(ffn(rows, e) * wt[:, None], mode="drop")
        return out, jnp.sum(idx < n, dtype=jnp.int32)

    def one_expert(e, carry):
        out, computed = carry
        branch = (counts[e] > 0).astype(jnp.int32) + (counts[e] > cap)
        out, done = jax.lax.switch(branch, (skip, gathered, full), e, out)
        return out, computed + done

    return jax.lax.fori_loop(
        0, counts.shape[0], one_expert, (jnp.zeros(u.shape, jnp.float32), jnp.zeros((), jnp.int32))
    )


def _shared_expert(mod, u, dtype, f):
    """The shared SwiGLU expert over every token [n, h]: operands in
    ``dtype``, float32 accumulation, the activation cast to ``dtype``
    before the down projection (ops/expert_ffn.py's precision); float32
    out."""
    h, f32 = u.shape[-1], jnp.float32
    init = nn.initializers.normal(h ** -0.5)
    gate = mod.param("shared_gate", init, (h, f)).astype(dtype)
    up = mod.param("shared_up", init, (h, f)).astype(dtype)
    down = mod.param("shared_down", nn.initializers.normal(f ** -0.5), (f, h)).astype(dtype)
    x = u.astype(dtype)
    act = jax.nn.silu(jnp.dot(x, gate, preferred_element_type=f32)) * jnp.dot(x, up, preferred_element_type=f32)
    return jnp.dot(act.astype(dtype), down, preferred_element_type=f32)


class ExpertLayer(nn.Module):
    """``config`` is the model's ``GPTConfig`` (``config.moe`` a
    ``MoeConfig``).  Parameters: ``router`` [h, E + Z], ``select_bias``
    [E + Z] (the buffer ``e_score_correction_bias``; zeros bias nothing),
    ``experts_gate`` / ``experts_up`` [held, h, f], ``experts_down``
    [held, f, h]."""

    config: Any

    @nn.compact
    def __call__(self, x, token_mask: Optional[jax.Array] = None):
        cfg, mc = self.config, self.config.moe
        if cfg.quant is not None or cfg.lora_rank is not None or cfg.lora_serve:
            raise ValueError("the expert layer (cfg.moe) is not supported with quant or LoRA")
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        lead, h = x.shape[:-1], x.shape[-1]
        n_held, f = len(mc.held), mc.expert_size
        u = x.reshape(-1, h)
        n = u.shape[0]
        valid = jnp.ones((n,), bool) if token_mask is None else token_mask.reshape(n)

        router = self.param("router", nn.initializers.normal(h ** -0.5), (h, mc.width), f32)
        bias = self.param("select_bias", nn.initializers.zeros, (mc.width,), f32)
        init = nn.initializers.normal(h ** -0.5)
        w_gate = self.param("experts_gate", init, (n_held, h, f)).astype(cfg.dtype)
        w_up = self.param("experts_up", init, (n_held, h, f)).astype(cfg.dtype)
        w_down = self.param("experts_down", nn.initializers.normal(f ** -0.5), (n_held, f, h)).astype(cfg.dtype)

        with jax.named_scope("moe.route"):
            logits = jnp.dot(u.astype(f32), router.astype(f32), precision=hi)
            p = jax.nn.sigmoid(logits) if mc.score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
            _, ids = jax.lax.top_k(p + bias.astype(f32), mc.top_k)  # [n, k]
            w = jnp.take_along_axis(p, ids, axis=-1)
            if mc.norm_topk:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            w = w * mc.scaling
            w = jnp.where(valid[:, None], w, 0.0)
            local_of = np.full((mc.width,), -1, np.int32)
            local_of[list(mc.held)] = np.arange(n_held)
            local = jnp.asarray(local_of)[ids]  # the held expert's index here, or -1
            hit = (local[:, :, None] == jnp.arange(n_held)) & valid[:, None, None]  # [n, k, held]
            held_w = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)  # [n, held]
            counts = jnp.sum(jnp.any(hit, axis=1), axis=0, dtype=jnp.int32)  # tokens of each held expert
            is_zero = (ids >= mc.n_routed) & valid[:, None]
            zero_w = jnp.sum(jnp.where(is_zero, w, 0.0), axis=1)  # [n]

        weight_of = held_w.T  # [held, n]
        with jax.named_scope("moe.experts"):
            if few_tokens(n):
                # An expert runs over all the tokens, or not at all.
                out, computed = expert_ffn(u, weight_of, counts, w_gate, w_up, w_down)
            else:
                out, computed = _many_token_experts(u, weight_of, counts, w_gate, w_up, w_down)
        if mc.n_zero:
            with jax.named_scope("moe.identity"):
                out = out + zero_w[:, None] * u.astype(f32)
        if mc.shared_size:
            with jax.named_scope("moe.shared"):
                out = out + _shared_expert(self, u, cfg.dtype, mc.shared_size)

        held_n, zero_n = jnp.sum(counts), jnp.sum(is_zero, dtype=jnp.int32)
        stats = jnp.concatenate([
            jnp.stack([
                held_n, zero_n, jnp.sum(valid, dtype=jnp.int32) * mc.top_k - held_n - zero_n,
                held_n - computed, jnp.sum(counts > 0, dtype=jnp.int32), jnp.any(valid).astype(jnp.int32),
            ]),
            counts,
        ])
        self.sow("moe_stats", "counts", stats)
        return out.astype(cfg.dtype).reshape(*lead, h)
