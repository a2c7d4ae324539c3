"""Builder's tool: the training cell's compared numbers over many seeds in
ONE process on the chip, at the cell's own size: the program's (the jitted
``make_train_step`` through its first steps, as a run drives it), and for
the first ``--control-seeds`` of them the int8 control and the planted
faults, each against the plain reference.  One JSON line per seed.

    python3 -m chipbench.tools.train_readings --config chipbench/configs/resnet50-b128.json \
        --first-seed 5000 --seeds 12 --control-seeds 3

``--float32 1 --learning-rate 0.01`` is the look at what the three steps do
to rounding; ``--memory 1`` lays the compiled step's own needs beside the
allocator's peaks (what ``memory_peak_bytes`` is made of).
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--first-seed", type=int, default=5000)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--float32", type=int, default=0,
                   help="the look: the program built in float32 at highest precision instead of as configured")
    p.add_argument("--learning-rate", type=float, default=None,
                   help="the look: another learning rate than the configuration's, for program and reference alike")
    p.add_argument("--memory", type=int, default=0,
                   help="also say what the compiled step needs (memory_analysis) beside the allocator's memory_stats")
    args = p.parse_args()
    with open(args.config) as f:
        model = json.load(f)
    if args.learning_rate is not None:
        model["learning_rate"] = args.learning_rate

    import jax

    from k8s_device_plugin_tpu.utils.platform import enable_compilation_cache

    from ..reference import resnet as ref
    from ..train_child import build, first_steps, seeded_state

    enable_compilation_cache(min_compile_seconds=0.0, log=lambda m: None)
    net, tx, step = build(model, float32=bool(args.float32))
    if args.float32:
        jax.config.update("jax_default_matmul_precision", "highest")
    follow = model["correct"]["follow_steps"]
    for i in range(args.seeds):
        seed, t0 = args.first_seed + i * 7919, time.monotonic()
        state, batch = seeded_state(model, seed, net, tx)
        if args.memory and i == 0:
            print(json.dumps({"memory_stats_before_the_step_is_loaded": jax.local_devices()[0].memory_stats()}), flush=True)
            need = step.lower(state, batch).compile().memory_analysis()
            print(json.dumps({"memory_analysis": {k: getattr(need, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")}}), flush=True)
        state, program = first_steps(step, state, batch, follow)
        if args.memory and i == 0:
            print(json.dumps({"memory_stats_after_the_first_steps": jax.local_devices()[0].memory_stats()}), flush=True)
        del state
        reference = ref.follow(model, seed, follow)
        worst: list[str] = []
        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "program_float32" if args.float32 else "program": ref.compare(program, reference, worst), "worst": worst}
        if i < args.control_seeds:
            for name, fault in (("control_int8", {"quant": "int8"}), ("fault_half_batch", {"half_batch": True})):
                row[name] = ref.compare(ref.follow(model, seed, follow, **fault), reference)
        row["seconds"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
