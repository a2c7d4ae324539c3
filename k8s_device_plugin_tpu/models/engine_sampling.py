"""Serving-engine sampling: per-slot filters and the jitted decode steps.

Split out of engine.py (round 4).  Everything here is a pure function of
its arguments — the builders take the decode-mode ``TransformerLM`` and
return jitted programs; nothing closes over engine state.  The engine
caches built programs per (variant key) on the instance (a process-global
cache would pin params/pools beyond the engine's lifetime).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .transformer import NEG_LOGIT


def _token_logprob(row, nxt):
    """The emitted token's logprob under the UNSCALED model distribution
    (sampler-independent semantics — temperature/top-k reshape what gets
    PICKED, not what is reported).  Compiled into a step variant only
    when a request asks (the ``want_lp`` key of build_step_fn /
    build_block_fn), so engines that never serve logprobs never compute
    it."""
    lp = jax.nn.log_softmax(row.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, nxt[:, None], axis=1)[:, 0]


def filter_top_k_top_p(scaled, top_k, top_p):
    """Mask ``scaled`` logits [batch, vocab] to each row's top-k tokens and
    smallest nucleus with mass >= top_p — with PER-ROW traced ``top_k``
    (int32, vocab = disabled) and ``top_p`` (float32, 1.0 = disabled), so
    slots with different sampler settings mix in one jitted step.

    `lax.top_k` needs a static k, so this uses one descending sort per row
    and reads thresholds out of it: the k-th value for top-k, and the
    smallest value still inside the nucleus for top-p (computed on the
    top-k-filtered distribution, the HF/vLLM filter order).  Keeping
    ``scaled >= threshold`` admits ties, matching sample_generate's
    static-k semantics (transformer.py).  O(vocab log vocab) on a
    [slots, vocab] array — noise next to the model forward.
    """
    vocab = scaled.shape[-1]
    s_sorted = jnp.sort(scaled, axis=-1)[:, ::-1]
    ranks = jnp.arange(vocab)[None, :]
    kth = jnp.take_along_axis(
        s_sorted, jnp.clip(top_k, 1, vocab)[:, None] - 1, axis=-1
    )
    in_k = ranks < jnp.clip(top_k, 1, vocab)[:, None]
    probs = jax.nn.softmax(jnp.where(in_k, s_sorted, NEG_LOGIT), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A rank is in the nucleus while the mass BEFORE it is < p (so the
    # first token is always kept); p = 1.0 keeps every unmasked rank.
    in_p = jnp.logical_and(in_k, (cum - probs) < top_p[:, None])
    p_min = jnp.min(
        jnp.where(in_p, s_sorted, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(
        scaled >= jnp.maximum(kth, p_min), scaled, NEG_LOGIT
    )


def variant_names(filtered: bool, biased: bool) -> list[str]:
    """Keyword names of the optional per-slot arrays a (filtered,
    biased) step/block variant takes, in signature order — the ONE
    place the ordering lives (builders zip *rest against it, call
    sites assemble arrays with ServingEngine._variant_arrays)."""
    names = []
    if filtered:
        names += ["topks", "topps"]
    if biased:
        names += ["bias_ids", "bias_vals"]
    return names


def _derived_tables(cache, chain, pos, page_size):
    """The visible page-table view, computed IN-PROGRAM from the full
    allocated chain: entries covering positions [0, pos] show their real
    page, later entries show scratch page 0 — the same lazy-frontier
    publication the engine used to perform with per-layer host scatters
    (engine_paging._extend_frontier), now one cheap elementwise op whose
    result every layer's cache entry shares.  The kernel's pipeline
    therefore never streams unwritten generation pages, and the host
    never dispatches a publication scatter."""
    mpp = chain.shape[1]
    table = jnp.where(
        jnp.arange(mpp, dtype=jnp.int32)[None, :] <= pos[:, 0:1] // page_size,
        chain,
        0,
    )
    return {
        name: {**layer, "attn": {**layer["attn"], "page_table": table}} if "attn" in layer else layer
        for name, layer in cache.items()
    }


def _moe_apply(model, chain):
    """What ``model.apply`` needs beyond the cache for a model with expert
    layers (models/moe.py): the ``moe_stats`` collection mutable, and the
    real-token mask.  A slot is live from its graft to its teardown, which
    is while its chain row names a first page (page 0 is the scratch page
    and is never handed out): an idle slot's row routes nowhere, touches no
    expert and counts nothing.  Nothing for a model without them: its
    program stays what it was."""
    if model.config.moe is None:
        return ["cache"], {}
    if chain is None:
        raise ValueError("a model with expert layers (cfg.moe) decodes with derived tables (no spec_gamma)")
    return ["cache", "moe_stats"], {"token_mask": chain[:, :1] > 0}


def moe_stats(mut) -> jax.Array:
    """The routing counts one ``model.apply`` sowed, [expert layers,
    MoeConfig.stats_width] int32, in layer order."""
    col = mut["moe_stats"]
    return jnp.stack([col[name]["moe"]["counts"][0] for name in sorted(col, key=lambda n: int(n.rsplit("_", 1)[1]))])


def pack_stats(out, stats):
    """The routing counts INSIDE the packed readback: flattened behind the
    tokens (and logprobs), in ``out``'s dtype (a block's count is far
    below 2**24, which float32 holds exactly), so the host still syncs ONE
    array a dispatch (ServingEngine._unpack splits it)."""
    return jnp.concatenate([out.reshape(-1), stats.reshape(-1).astype(out.dtype)])


def build_step_fn(model, filtered: bool, want_lp: bool, biased: bool = False,
                  derive_tables: bool = False):
    """Build the jitted single-token decode step.  ``filtered`` compiles
    the top-k/top-p sort in; ``want_lp`` compiles the [slots, vocab]
    log-softmax + gather whose result logprobs requests read; ``biased``
    compiles the [slots, MAX_BIAS] scatter-add of per-slot logit biases
    onto the picking row (reported logprobs stay unbiased).

    Returns ``(out, next_tokens, next_positions, next_key, cache)``.
    ``out`` is the step's PACKED device→host readback: the [slots] int32
    token vector alone when ``want_lp`` is off (no logprob compute, no
    second transfer — no consumer would read it), else one [2, slots]
    float32 array carrying tokens in row 0 and their logprobs in row 1,
    so the host syncs a single array per step either way (float32 holds
    token ids exactly below 2^24 — far beyond any realistic vocab).
    The last three returns are the NEXT step's inputs, computed
    in-program so a steady-state decode loop feeds device outputs
    straight back in — no per-step host->device uploads, no separate
    key-split dispatch (the engine's device-resident step state; it
    rebuilds from host lists only when slot structure changes).

    ``derive_tables``: take a ``chain`` argument (the full allocated page
    chain, [slots, max_pages_per_seq]) and compute the visible page-table
    view in-program (_derived_tables) instead of reading host-published
    cache tables — the engine enables this for non-speculative engines."""
    page_size = model.config.paged.page_size if derive_tables else None

    # Variant signatures omit the arrays their feature compiled out:
    # an unused jit argument is still transferred every dispatch, and
    # the greedy/temperature-only path (the common case) shouldn't
    # pay host->device uploads for filters/biases it never applies.
    def _core(params, cache, tokens, positions, temps, aids, key,
              chain=None,
              topks=None, topps=None, bias_ids=None, bias_vals=None):
        key, sub = jax.random.split(key)
        if derive_tables:
            with jax.named_scope("derive_tables"):
                cache = _derived_tables(cache, chain, positions, page_size)
        mutable, more = _moe_apply(model, chain)
        logits, mut = model.apply(
            {"params": params, "cache": cache},
            tokens,
            positions,
            adapter_ids=aids,
            mutable=mutable,
            **more,
        )
        # Sampling lies outside every Flax module: a scope of its own
        # names its operations in a device trace.
        with jax.named_scope("sample"):
            row = logits[:, -1, :]
            pick = row
            if biased:
                rows = jnp.arange(row.shape[0])[:, None]
                pick = row.at[rows, bias_ids].add(
                    bias_vals.astype(row.dtype)
                )
            greedy = jnp.argmax(pick, axis=-1).astype(jnp.int32)
            # One categorical over the batch samples each row
            # independently; temp<=0 rows take the argmax (their scaled
            # logits are unused).
            scaled = pick / jnp.where(temps > 0, temps, 1.0)[:, None]
            if filtered:
                scaled = filter_top_k_top_p(scaled, topks, topps)
            sampled = jax.random.categorical(sub, scaled).astype(jnp.int32)
            nxt = jnp.where(temps > 0, sampled, greedy)
            out = (
                jnp.stack(
                    [nxt.astype(jnp.float32), _token_logprob(row, nxt)]
                )
                if want_lp
                else nxt
            )
            if "moe_stats" in mut:
                out = pack_stats(out, moe_stats(mut))
        return out, nxt[:, None], positions + 1, key, mut["cache"]

    extra = (["chain"] if derive_tables else []) + variant_names(
        filtered, biased
    )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, tokens, positions, temps, aids, key, *rest):
        return _core(
            params, cache, tokens, positions, temps, aids, key,
            **dict(zip(extra, rest)),
        )

    return step


def build_block_fn(model, T: int, filtered: bool, want_lp: bool,
                   biased: bool = False, derive_tables: bool = False):
    """Build the jitted T-step decode block: a lax.scan of T exact
    single-token decode steps — same model apply, same per-slot sampling,
    a fresh subkey per step — so one dispatch advances every active slot
    T tokens.  Greedy slots emit exactly their step-at-a-time decode;
    sampled slots draw from the identical per-step distributions
    (different key schedule than T separate step() calls, same law).

    Returns ``(out, next_tokens, next_positions, next_key, cache)`` —
    same packed-readback and feed-forward contract as build_step_fn,
    with ``out`` shaped [slots, T] int32 (tokens only) or [2, slots, T]
    float32 (tokens + logprobs) when ``want_lp`` is on.
    ``derive_tables``: per-iteration in-program publication
    from the chain (the scan's running position naturally publishes each
    page exactly as the write frontier reaches it — the host used to
    pre-publish the whole block's lookahead)."""
    page_size = model.config.paged.page_size if derive_tables else None

    def _core(params, cache, tokens, positions, temps, aids, key,
              chain=None,
              topks=None, topps=None, bias_ids=None, bias_vals=None):
        key, sub = jax.random.split(key)

        mutable, more = _moe_apply(model, chain)
        moe = model.config.moe

        def body(carry, k):
            cache, toks, pos, *stats = carry
            if derive_tables:
                with jax.named_scope("derive_tables"):
                    cache = _derived_tables(cache, chain, pos, page_size)
            logits, mut = model.apply(
                {"params": params, "cache": cache},
                toks,
                pos,
                adapter_ids=aids,
                mutable=mutable,
                **more,
            )
            if moe is not None:
                # The counts ride the carry and leave with the tokens.
                stats = [stats[0] + moe_stats(mut)]
            with jax.named_scope("sample"):
                row = logits[:, -1, :]
                pick = row
                if biased:
                    rows = jnp.arange(row.shape[0])[:, None]
                    pick = row.at[rows, bias_ids].add(
                        bias_vals.astype(row.dtype)
                    )
                greedy = jnp.argmax(pick, axis=-1).astype(jnp.int32)
                scaled = pick / jnp.where(temps > 0, temps, 1.0)[:, None]
                if filtered:
                    scaled = filter_top_k_top_p(scaled, topks, topps)
                sampled = jax.random.categorical(k, scaled).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
                ys = (nxt, _token_logprob(row, nxt)) if want_lp else nxt
            return (mut["cache"], nxt[:, None], pos + 1, *stats), ys

        stats0 = []
        if moe is not None:
            stats0 = [jnp.zeros((model.config.expert_layers, moe.stats_width), jnp.int32)]
        (cache, last_tok, last_pos, *stats), ys = jax.lax.scan(
            body, (cache, tokens, positions, *stats0), jax.random.split(sub, T)
        )
        if want_lp:
            toks, lps = ys
            out = jnp.stack([toks.T.astype(jnp.float32), lps.T])
        else:
            out = ys.T  # [slots, T]
        if stats:
            out = pack_stats(out, stats[0])
        return out, last_tok, last_pos, key, cache

    # Same variant-signature split as build_step_fn: the common path
    # shouldn't upload filter/bias arrays it compiled out.
    extra = (["chain"] if derive_tables else []) + variant_names(
        filtered, biased
    )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def block(params, cache, tokens, positions, temps, aids, key, *rest):
        return _core(
            params, cache, tokens, positions, temps, aids, key,
            **dict(zip(extra, rest)),
        )

    return block
