"""Serving-engine speculative decoding: shared-pool self-speculation.

Split out of engine.py (round 4).  ``build_spec_rounds`` is a pure
builder (no engine state captured); ``SpeculativeMixin`` carries the
host-side round consumption that ServingEngine mixes in.  The algorithm
(Leviathan/Chen acceptance-rejection over the shared paged pool) is
documented on the builders below; models/speculative.py holds the
standalone dense-cache variant.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.spans import ENGINE_TRACE
from .engine_sampling import filter_top_k_top_p
from .engine_types import Request


def build_spec_rounds(model, draft_model, layer_names: list[str], gamma: int):
    """Build the two jitted speculative-round programs:
    ``(spec_round, spec_round_plain)`` — the full sampled/mixed round and
    the greedy-only fast path (no filter sorts, no softmaxes, no stacked
    Q distributions; _spec_step dispatches host-side on whether any
    active slot samples)."""

    @functools.partial(jax.jit, donate_argnums=(2,))
    def spec_round(
        params, dparams, cache, tokens, positions, temps, topks,
        topps, key,
    ):
        """One speculative round for every slot at once.

        tokens/positions: [slots, 1] (positions = each row's
        current length L).  gamma draft steps propose
        d_1..d_gamma per slot (writing draft K/V at L..L+gamma-1),
        then ONE (gamma+1)-token target pass scores
        [last, d_1..d_gamma] at L..L+gamma — overwriting every
        draft-written slot with exact target K/V, which is what
        makes the shared pool sound.

        Greedy slots (temp <= 0) use longest-agreeing-prefix
        verification (output exactly the greedy decode); sampled
        slots use Leviathan/Chen acceptance-rejection over the
        SAME per-slot temperature/top-k/top-p filter the ordinary
        step applies (accept d w.p. min(1, P(d)/Q(d)); first
        rejection resamples the residual max(0, P-Q), full accept
        samples the bonus from P) — marginally exact filtered
        target sampling, mixed freely in one batch.

        Returns (emitted [slots, gamma+1], a [slots], cache):
        row s's round tokens are emitted[s, :a[s]+1]; length
        rewind is host bookkeeping.
        """
        kd, ka, kt = jax.random.split(key, 3)
        sampling = temps > 0  # [slots]
        safe_t = jnp.where(sampling, temps, 1.0)[:, None]

        def d_step(carry, i):
            c, tok = carry
            logits, mut = draft_model.apply(
                {"params": dparams, "cache": c},
                tok,
                positions + i,
                mutable=["cache"],
            )
            row = logits[:, -1, :]
            greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
            filt = filter_top_k_top_p(row / safe_t, topks, topps)
            samp = jax.random.categorical(
                jax.random.fold_in(kd, i), filt
            ).astype(jnp.int32)
            nxt = jnp.where(sampling, samp, greedy)[:, None]
            q = jax.nn.softmax(filt, axis=-1)  # draft dist Q_i
            return (mut["cache"], nxt), (nxt[:, 0], q)

        (cache, _), (props_t, q_t) = jax.lax.scan(
            d_step, (cache, tokens), jnp.arange(gamma)
        )
        props = props_t.T  # [slots, gamma]
        qs = jnp.moveaxis(q_t, 0, 1)  # [slots, gamma, vocab]
        # The draft advanced every row's seq_lens to L+gamma;
        # rewind to L so the verify append writes L..L+gamma.
        L = positions[:, 0]
        cache = {
            name: {
                **cache[name],
                "attn": {**cache[name]["attn"], "seq_lens": L},
            }
            for name in layer_names
        }
        block = jnp.concatenate([tokens, props], axis=1)
        block_pos = positions + jnp.arange(gamma + 1)[None, :]
        v_logits, mut = model.apply(
            {"params": params, "cache": cache},
            block,
            block_pos,
            mutable=["cache"],
        )  # [slots, gamma+1, vocab]
        slots, vocab = v_logits.shape[0], v_logits.shape[2]
        v_filt = filter_top_k_top_p(
            (v_logits / safe_t[..., None]).reshape(-1, vocab),
            jnp.repeat(topks, gamma + 1),
            jnp.repeat(topps, gamma + 1),
        ).reshape(slots, gamma + 1, vocab)
        p = jax.nn.softmax(v_filt, axis=-1)  # target dist P_j

        # Greedy acceptance: longest prefix agreeing with argmax.
        t_greedy = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
        match_g = (props == t_greedy[:, :gamma]).astype(jnp.int32)
        a_g = jnp.sum(jnp.cumprod(match_g, axis=1), axis=1)
        # Sampling acceptance-rejection.
        p_d = jnp.take_along_axis(
            p[:, :gamma], props[..., None], axis=-1
        )[..., 0]
        q_d = jnp.take_along_axis(qs, props[..., None], axis=-1)[
            ..., 0
        ]
        u = jax.random.uniform(ka, (slots, gamma))
        accept = (u * q_d < p_d).astype(jnp.int32)
        a_s = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
        a = jnp.where(sampling, a_s, a_g)  # [slots]

        # Tail token at position a: correction/bonus.  Sampled
        # slots draw from the residual max(0, P_a - Q_a) (full
        # accept: Q_gamma := 0 so the residual is P_gamma itself).
        p_a = jnp.take_along_axis(p, a[:, None, None], axis=1)[:, 0]
        qs_pad = jnp.concatenate(
            [qs, jnp.zeros((slots, 1, vocab), qs.dtype)], axis=1
        )
        q_a = jnp.take_along_axis(qs_pad, a[:, None, None], axis=1)[
            :, 0
        ]
        resid = jnp.where(
            (a < gamma)[:, None], jnp.clip(p_a - q_a, min=0.0), p_a
        )
        norm = jnp.sum(resid, axis=-1, keepdims=True)
        tail_p = jnp.where(norm > 0, resid / norm, p_a)
        tail_samp = jax.random.categorical(
            kt, jnp.log(tail_p)
        ).astype(jnp.int32)
        tail_greedy = jnp.take_along_axis(t_greedy, a[:, None], 1)[
            :, 0
        ]
        tail = jnp.where(sampling, tail_samp, tail_greedy)
        idxs = jnp.arange(gamma + 1)[None, :]
        props_pad = jnp.concatenate(
            [props, jnp.zeros((slots, 1), jnp.int32)], axis=1
        )
        emitted = jnp.where(idxs < a[:, None], props_pad, tail[:, None])
        return emitted, a, mut["cache"]

    # Plain greedy round — no filter sorts, no softmaxes, no
    # stacked Q distributions.  Same step_plain rationale: a spec
    # engine serving only greedy requests (the CLI default) must
    # not pay the sampler machinery every round; _spec_step
    # dispatches host-side on whether any active slot samples.
    @functools.partial(jax.jit, donate_argnums=(2,))
    def spec_round_plain(params, dparams, cache, tokens, positions):
        def d_step(carry, i):
            c, tok = carry
            logits, mut = draft_model.apply(
                {"params": dparams, "cache": c},
                tok,
                positions + i,
                mutable=["cache"],
            )
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                jnp.int32
            )[:, None]
            return (mut["cache"], nxt), nxt[:, 0]

        (cache, _), props_t = jax.lax.scan(
            d_step, (cache, tokens), jnp.arange(gamma)
        )
        props = props_t.T
        L = positions[:, 0]
        cache = {
            name: {
                **cache[name],
                "attn": {**cache[name]["attn"], "seq_lens": L},
            }
            for name in layer_names
        }
        block = jnp.concatenate([tokens, props], axis=1)
        block_pos = positions + jnp.arange(gamma + 1)[None, :]
        v_logits, mut = model.apply(
            {"params": params, "cache": cache},
            block,
            block_pos,
            mutable=["cache"],
        )
        slots = v_logits.shape[0]
        t_greedy = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
        match = (props == t_greedy[:, :gamma]).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        tail = jnp.take_along_axis(t_greedy, a[:, None], 1)[:, 0]
        props_pad = jnp.concatenate(
            [props, jnp.zeros((slots, 1), jnp.int32)], axis=1
        )
        emitted = jnp.where(
            jnp.arange(gamma + 1)[None, :] < a[:, None],
            props_pad,
            tail[:, None],
        )
        return emitted, a, mut["cache"]

    return spec_round, spec_round_plain


class SpeculativeMixin:
    """Host-side speculative round consumption, mixed into ServingEngine
    (which owns every attribute referenced here)."""

    def _spec_step(self, active: list[int], finished: list[Request]) -> list[Request]:
        """One speculative round: gamma draft steps + one verify pass
        advance every active slot by 1..gamma+1 tokens.  Greedy slots
        emit EXACTLY their non-speculative greedy decode; sampled slots
        emit marginally exact filtered target samples (both pinned in
        tests/test_engine.py); speculation changes only the schedule."""
        with self.profiler.phase("spec_verify"):
            active = self._ensure_frontier(active, self._spec_gamma)
            if not active:
                self._update_gauges()
                return finished
            round_t0 = time.monotonic()
            tokens = jnp.asarray(self._slot_last, jnp.int32)[:, None]
            positions = jnp.asarray(self._slot_len, jnp.int32)[:, None]
            if any(
                self.slots[s] is not None and self._slot_temp[s] > 0
                for s in range(self.max_slots)
            ):
                temps = jnp.asarray(self._slot_temp, jnp.float32)
                topks = jnp.asarray(self._slot_topk, jnp.int32)
                topps = jnp.asarray(self._slot_topp, jnp.float32)
                self._rng, sub = jax.random.split(self._rng)
                emitted, a_vec, self.cache = self._spec_round(
                    self.params, self.draft_params, self.cache, tokens,
                    positions, temps, topks, topps, sub,
                )
            else:
                emitted, a_vec, self.cache = self._spec_round_plain(
                    self.params, self.draft_params, self.cache, tokens, positions
                )
            emitted = np.asarray(emitted)
            a_vec = np.asarray(a_vec)
        with self.profiler.phase("sample"):
            now = time.monotonic()
            if self.spans:
                # One engine-scoped span per draft+verify round: acceptance
                # attrs make a low-acceptance regime visible right next to
                # the round's wall time in /debug/state.
                self.spans.record_span(
                    "spec.verify",
                    ENGINE_TRACE,
                    start_monotonic=round_t0,
                    end_monotonic=now,
                    attrs={
                        "slots": len(active),
                        "proposed": int(self._spec_gamma) * len(active),
                        "accepted": int(sum(a_vec[s] for s in active)),
                    },
                )
            gamma = self._spec_gamma
            emitted_total = 0
            for s in active:
                req = self.slots[s]
                a = int(a_vec[s])
                # Emit d_1..d_a then the target's own token at position a
                # (correction on rejection, bonus on full accept).  All a+1
                # tokens are consumed unless a finish condition truncates —
                # and truncation only ever coincides with req.done, so live
                # slots always consume exactly a+1.
                self.spec_proposed += gamma
                self.spec_accepted += a
                if self.metrics:
                    self.metrics.spec_proposed.inc(gamma)
                    self.metrics.spec_accepted.inc(a)
                    if gamma > a:
                        self.metrics.spec_rejected.inc(gamma - a)
                round_toks = [int(emitted[s, j]) for j in range(a + 1)]
                consumed = 0
                for tok in round_toks:
                    req.tokens.append(tok)
                    self._slot_last[s] = tok
                    consumed += 1
                    emitted_total += 1
                    if (
                        len(req.tokens) >= req.max_new_tokens
                        or (self.eos_id is not None and tok == self.eos_id)
                        or self._hit_stop(req)
                    ):
                        break
                self._slot_len[s] += consumed
                self._observe_itl(s, consumed, now)
                self._maybe_finish(s)
                if req.done:
                    finished.append(req)
                else:
                    self._extend_frontier(s)
                    if self.cfg.attention_window is not None:
                        self._reclaim_windowed(s)
            # The round left every row's device length at L+gamma+1; re-align
            # all rows to the host truth in one vector write per layer (idle
            # and just-cleared rows are 0 in _slot_len, matching _clear_slot).
            # A FRESH array per layer: sharing one across layers would hand
            # the next round's donation the same buffer twice, which XLA
            # rejects (donate(a), donate(a)).
            for name in self._layer_names:
                att = self.cache[name]["attn"]
                self.cache[name]["attn"] = {
                    **att,
                    "seq_lens": jnp.array(self._slot_len, jnp.int32),
                }
            # Rounds advance each slot by a data-dependent 1..gamma+1: the
            # device-resident step state cannot be fed forward (engine.py).
            self._mark_state_dirty()
            self._step_tokens += emitted_total
            if self.metrics:
                self.metrics.steps.inc()
                self.metrics.tokens.inc(emitted_total)
            self._update_gauges()
        return finished
