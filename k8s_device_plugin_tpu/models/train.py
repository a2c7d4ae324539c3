"""Training-step factories for the benchmark workloads.

Pure-functional train steps built for XLA: state in, state out, no Python
control flow on traced values, dropout rngs folded from the step counter so a
step is a deterministic function of (state, batch).  Everything here works
unchanged under jit on one chip or pjit over a mesh (parallel/sharding.py
supplies the shardings).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct


class TrainState(struct.PyTreeNode):
    """Minimal train state: params + optimizer + (optional) BatchNorm stats."""

    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any  # empty dict for stat-less models

    def with_updates(self, **kwargs) -> "TrainState":
        return self.replace(**kwargs)


def create_train_state(
    rng: jax.Array,
    model: nn.Module,
    sample_batch: dict,
    tx: optax.GradientTransformation,
    input_key: str = "images",
) -> TrainState:
    variables = model.init(rng, sample_batch[input_key])
    params = variables["params"]
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        batch_stats=variables.get("batch_stats", {}),
    )


def _takes_train_kwarg(model: nn.Module) -> bool:
    import inspect

    return "train" in inspect.signature(type(model).__call__).parameters


def _apply(model, state, params, x, train, rngs, capture_intermediates=False):
    """Model apply that tolerates models with/without batch_stats and the
    `train` kwarg (image models take it; BERT does not).  The kwarg decision
    is static (signature inspection), never a traced-time fallback.

    Returns (out, new_batch_stats, intermediates); the last is {} unless
    `capture_intermediates` asks for the 'intermediates' collection (where
    MoE layers sow their load-balance loss — sow is a silent no-op unless
    the collection is marked mutable here)."""
    variables = {"params": params}
    kwargs = {"train": train} if _takes_train_kwarg(model) else {}
    mutable = []
    if bool(state.batch_stats):
        variables["batch_stats"] = state.batch_stats
        mutable.append("batch_stats")
    if capture_intermediates:
        mutable.append("intermediates")
    if mutable:
        out, mutated = model.apply(
            variables, x, mutable=mutable, rngs=rngs, **kwargs
        )
        return out, mutated.get("batch_stats", {}), mutated.get("intermediates", {})
    return model.apply(variables, x, rngs=rngs, **kwargs), {}, {}


def sown_aux_loss(intermediates: Any) -> jax.Array:
    """Sum every leaf sown under a name containing 'aux_loss' (e.g. each MoE
    layer's `moe_aux_loss`).  Returns a scalar (0.0 when none exist)."""
    total = jnp.zeros(())
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if any("aux_loss" in str(getattr(k, "key", k)) for k in path):
            total = total + jnp.sum(leaf)
    return total


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def make_train_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    input_key: str = "images",
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array] = softmax_xent,
    aux_loss_coeff: float = 0.0,
    grad_accum: int = 1,
) -> Callable[[TrainState, dict], tuple[TrainState, jax.Array]]:
    """Build `(state, batch) -> (state, loss)`; jit/pjit it at the call site.

    aux_loss_coeff > 0 makes the 'intermediates' collection mutable and adds
    `coeff * sum(sown *aux_loss*)` to the loss — REQUIRED for MoE models
    (parallel/moe.py sows `moe_aux_loss` per layer; without this the router
    trains with no load balancing).  GShard/Switch use coeff ≈ 0.01.

    grad_accum > 1 splits the batch into that many microbatches and runs
    them through ONE `lax.scan` inside the step, averaging the f32 grads
    before a single optimizer update — the standard large-effective-batch
    /small-memory trade, TPU-shaped: activation memory is one
    microbatch's, the scan is a single compiled program (no per-micro
    dispatch), and the update math equals the full-batch step up to
    summation order.  The batch's leading dim must divide evenly.
    BatchNorm models keep per-micro running-stat updates (stats carry
    through the scan — the same sequential semantics as feeding the
    microbatches as separate steps); dropout folds a distinct rng per
    microbatch."""
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

    def compute_loss(params, state, micro, dropout_rng):
        def inner(p):
            logits, new_stats, inters = _apply(
                model,
                state,
                p,
                micro[input_key],
                train=True,
                rngs={"dropout": dropout_rng},
                capture_intermediates=aux_loss_coeff > 0.0,
            )
            loss = loss_fn(logits, micro["labels"])
            if aux_loss_coeff > 0.0:
                loss = loss + aux_loss_coeff * sown_aux_loss(inters)
            return loss, new_stats

        return jax.value_and_grad(inner, has_aux=True)(params)

    def train_step(state: TrainState, batch: dict):
        dropout_rng = jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        if grad_accum == 1:
            (loss, new_stats), grads = compute_loss(
                state.params, state, batch, dropout_rng
            )
        else:
            n = batch[input_key].shape[0]
            if n % grad_accum:
                raise ValueError(
                    f"batch size {n} is not divisible by grad_accum "
                    f"{grad_accum}"
                )
            micros = jax.tree.map(
                lambda x: x.reshape(
                    (grad_accum, x.shape[0] // grad_accum) + x.shape[1:]
                ),
                batch,
            )

            def body(carry, micro):
                stats, grad_sum, loss_sum, i = carry
                rng_i = jax.random.fold_in(dropout_rng, i)
                (loss_i, stats), grads_i = compute_loss(
                    state.params,
                    state.with_updates(batch_stats=stats),
                    micro,
                    rng_i,
                )
                grad_sum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grad_sum, grads_i
                )
                return (stats, grad_sum, loss_sum + loss_i, i + 1), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (new_stats, grad_sum, loss_sum, _), _ = jax.lax.scan(
                body,
                (state.batch_stats, zero_grads, jnp.float32(0.0), jnp.int32(0)),
                micros,
            )
            grads = jax.tree.map(
                lambda p, g: (g / grad_accum).astype(p.dtype),
                state.params,
                grad_sum,
            )
            loss = loss_sum / grad_accum
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (
            state.with_updates(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                batch_stats=new_stats,
            ),
            loss,
        )

    return train_step


def make_fused_lm_train_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    chunk: Optional[int] = None,
):
    """Decoder-LM train step whose loss tail is the fused LM-head +
    cross-entropy (ops/fused_xent.py): the model runs with
    ``output="hidden"`` and the head kernel is applied chunk-wise inside
    the loss, so the [batch, seq, vocab] float32 logits tensor — the peak
    HBM site of LM training — never materializes.  The head's parameters
    still live at params["lm_head"]["kernel"] (initialized by the normal
    logits path), so checkpoints are interchangeable with the standard
    step.  ``chunk`` needs no relation to the vocab size (the op pads and
    masks the ragged tail).

    This is a MEMORY lever, not a speed lever: a chunk sweep (b8 s1024
    vocab 32k; builder session 2026-08-01, record deleted in PR 21, not
    re-measured) saw 0.95x/0.98x/0.99x naive throughput at chunk = vocab/8, vocab/2, vocab — the scan tail
    never beats the one-shot matmul it replaces.  The default
    ``chunk=None`` resolves to vocab//2, the measured sweet spot: 2x
    logits-memory cut for ~2% throughput; pass a small explicit chunk
    when vocab-scaled memory is the binding constraint.
    """
    from ..ops.fused_xent import fused_linear_xent

    def train_step(state: TrainState, batch: dict):
        def compute_loss(params):
            hidden = model.apply(
                {"params": params}, batch["input_ids"], output="hidden"
            )
            b, s, d = hidden.shape
            w = params["lm_head"]["kernel"]
            return fused_linear_xent(
                hidden.reshape(b * s, d).astype(w.dtype),
                w,
                batch["labels"].reshape(b * s),
                chunk if chunk is not None else max(256, w.shape[1] // 2),
            )

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        return (
            state.with_updates(
                step=state.step + 1,
                params=optax.apply_updates(state.params, updates),
                opt_state=new_opt_state,
            ),
            loss,
        )

    return train_step


def make_eval_step(
    model: nn.Module, input_key: str = "images"
) -> Callable[[TrainState, dict], jax.Array]:
    def eval_step(state: TrainState, batch: dict):
        logits, _, _ = _apply(model, state, state.params, batch[input_key], train=False, rngs=None)
        return logits

    return eval_step
