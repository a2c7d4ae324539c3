"""The training cell's child: ``make_train_step`` of models/train.py on a
``TrainState``, jitted once, driven from ``--seed`` through its first steps
(which the plain reference follows afterwards) and then handed, the same
object, to the window of back-to-back steps.

The state's leaves are made from the seed by the benchmark
(chipbench/reference/resnet.py ``init``), not by the program, so that the
reference can make the same ones without taking anything the program made.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build(model: dict, float32: bool = False):
    """The network, the optimizer and the jitted step as models/benchmark.py
    --model resnet50 builds them.  ``float32`` (builder's look only,
    tools/train_readings.py) builds the same program in float32, the second
    witness of what bfloat16 rounding does to the compared numbers."""
    import jax
    import jax.numpy as jnp
    import optax

    from k8s_device_plugin_tpu.models.resnet import ResNet
    from k8s_device_plugin_tpu.models.train import make_train_step

    net = ResNet(
        stage_sizes=tuple(model["stage_sizes"]), num_classes=model["num_classes"],
        width=model["width"], stem=model["stem"],
        **({"dtype": jnp.float32, "norm_dtype": jnp.float32} if float32 else {"dtype": jnp.bfloat16}),
    )
    tx = optax.sgd(model["learning_rate"], momentum=model["momentum"])
    return net, tx, jax.jit(make_train_step(net, tx), donate_argnums=(0,))


def seeded_state(model: dict, seed: int, net, tx):
    """The program's own TrainState with the seed's leaves in it, and the
    seed's batch.  The state's layout comes from ``create_train_state`` as
    shapes only: its values are replaced, and computing them leaf by leaf
    took most of a minute on the chip."""
    import jax
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.train import create_train_state

    from .reference import resnet as ref

    params0, stats0, batch = ref.seeded(model, seed)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), net, {"images": batch["images"][:1]}, tx)
    )
    if jax.tree.structure(state.params) != jax.tree.structure(params0) or any(
        a.shape != b.shape for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params0))
    ):
        raise SystemExit("the benchmark's parameter layout is not the program's")
    state = state.replace(step=jnp.zeros((), jnp.int32), params=params0, batch_stats=stats0,
                          opt_state=jax.jit(tx.init)(params0))
    return state, batch


def first_steps(step, state, feed, follow: int):
    """The first steps, through the window's own call and feed: each
    step's loss, the first gradient's norms as the optimizer got it, the
    norms of the change after the last."""
    import jax
    import jax.numpy as jnp

    from .reference import resnet as ref

    kept0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(state.params)
    program = {"losses": []}
    for i in range(follow):
        state, loss = step(state, feed)
        program["losses"].append(float(loss))
        if i == 0:
            # optax.sgd with momentum: after one step the trace IS the
            # gradient as the optimizer got it.
            program["grad_norms"] = ref.leaf_norms(state.opt_state[0].trace)
    program["change_norms"] = ref.change_norms(state.params, kept0)
    return state, program


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="chipbench-train-child")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--platform", required=True)
    p.add_argument("--chips", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--t-process", type=float, required=True)
    p.add_argument("--control", type=int, default=0)
    p.add_argument("--fault", default="", help="tests only: frozen | half_batch")
    args = p.parse_args(argv)
    with open(args.config) as f:
        model = json.load(f)
    say = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731

    import jax
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.utils.platform import device_facts, enable_compilation_cache

    from . import trace as trace_mod
    from .reference import resnet as ref
    from .serve_child import memory_peak_bytes
    from .stats import capture_span

    enable_compilation_cache(min_compile_seconds=0.0, log=say)
    facts = device_facts()
    if facts["platform"] != args.platform or facts["device_count"] < args.chips:
        raise SystemExit(f"asked for {args.chips} x {args.platform}, JAX found {facts}")
    if args.chips != 1:
        raise SystemExit("the training child drives one chip")
    t_mark = [time.monotonic()]

    def lap() -> float:
        t_mark.append(time.monotonic())
        return t_mark[-1] - t_mark[-2]

    net, tx, step = build(model)
    state, batch = seeded_state(model, args.seed, net, tx)
    t_state = lap()
    feed = batch
    if args.fault == "half_batch":
        feed = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
    elif args.fault == "frozen":
        real = step
        step = lambda s, b: (s, real(jax.tree.map(jnp.copy, s), b)[1])  # noqa: E731

    follow = model["correct"]["follow_steps"]
    state, program = first_steps(step, state, feed, follow)
    state, loss = step(state, feed)
    loss.block_until_ready()

    # The window: steps back to back, one in flight ahead of the one waited for.
    t0 = time.monotonic()
    setup_s = t0 - args.t_process
    say(f"train child: set-up {setup_s:.1f} s: to JAX {t_mark[0] - args.t_process:.1f}, "
        f"state and batch {t_state:.1f}, the step's compile and first steps {lap():.1f}")
    tracing, reduced, notes = None, None, []
    trace_offset, trace_len = capture_span(args.seconds)
    trace_at = t0 + trace_offset
    trace_dir = os.path.join(args.run_dir, "trace")
    steps, prev, untraced = 0, None, None
    while True:
        now = time.monotonic()
        if args.trace and tracing is None and now >= trace_at:
            # The steps so far ran with the profiler off: their rate is the
            # step's own (under the profiler a step took twice as long on
            # the chip, and starting and stopping it stalls the loop).
            untraced = (steps, now - t0)
            jax.profiler.start_trace(trace_dir)
            tracing = time.monotonic()
        if tracing and tracing > 0 and now >= tracing + trace_len:
            jax.profiler.stop_trace()
            tracing = -1.0
        if now >= t0 + args.seconds:
            break
        state, loss = step(state, feed)
        if prev is not None:
            prev.block_until_ready()
            steps += 1
        prev = loss
    prev.block_until_ready()
    steps += 1
    elapsed = time.monotonic() - t0
    if tracing and tracing > 0:
        jax.profiler.stop_trace()
    images_per_s = steps * feed["images"].shape[0] / elapsed
    final_loss = float(prev)
    peak = memory_peak_bytes(jax, lambda m: say(f"train child: {m}"))
    del state, step, prev, loss

    if args.trace:
        path = trace_mod.find_xplane(trace_dir)
        reduced = trace_mod.reduce(trace_mod.load(path)) if path else None
        if reduced is None:
            notes.append("the capture holds no device operation")
        else:
            reduced.pop("op_seconds", None)
            # One step's device time under the profiler (the median event).
            names = tuple(model["programs"]["step"])
            took = sorted(d for name, d, _ in reduced["program_events"] if name.startswith(names))
            if took:
                reduced["step_device_ms"] = took[len(took) // 2] * 1e3

    # The reference, once the program's state is freed.
    t_ref = time.monotonic()
    reference = ref.follow(model, args.seed, follow)
    worst: list[str] = []
    numbers = ref.compare(program, reference, worst)
    for words in worst:
        say(f"train child: worst leaves, {words}")
    limits = model["correct"]["limits"]
    compared = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    compared["final_loss"] = {"value": final_loss, "limit": None}
    controls = {}
    if args.control:
        # The reference in int8 and with half the batch, put in the program's
        # place and held to the same limits (run.py judges them like the
        # program's numbers; each has to come out not correct).
        for name, fault in (("control_int8", {"quant": "int8"}), ("fault_half_batch", {"half_batch": True})):
            numbers = ref.compare(ref.follow(model, args.seed, follow, **fault), reference)
            controls[name] = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    compared["reference_s"] = {"value": time.monotonic() - t_ref, "limit": None}
    with open(args.out, "w") as f:
        json.dump({
            "setup_s": setup_s, "steps": steps, "elapsed_s": elapsed, "images_per_s": images_per_s,
            "untraced_images_per_s": untraced[0] * feed["images"].shape[0] / untraced[1] if untraced and untraced[0] else None,
            "device": {"platform": facts["platform"], "kind": facts["device_kind"],
                       "count": args.chips, "memory_peak_bytes": peak},
            "trace": reduced, "trace_notes": notes, "compared": compared, "controls": controls,
        }, f)
    say(f"train child: {steps} steps in {elapsed:.2f} s, {images_per_s:.1f} images/s"
        + (f"; before the capture {untraced[0]} steps in {untraced[1]:.2f} s; a traced step's device time "
           f"{(reduced or {}).get('step_device_ms')} ms" if untraced else ""))


if __name__ == "__main__":
    main()
