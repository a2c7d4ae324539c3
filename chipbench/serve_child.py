"""The replica a serving cell measures: ``ServingEngine`` and
``EngineServer`` built as ``models/http_server.py`` ``main()`` builds them,
from a configuration file and ``--seed``.

The shipped ``main()`` cannot express a public model (``intermediate_size =
hidden * 3``, no window, no ``rope_theta``, ``PRNGKey(0)``, float32
parameters), and the benchmark may not edit the program; so the
configuration's family (``families/<name>.py``, ``llm`` where the file
names none) builds ``GPTConfig`` and ``PagedConfig`` from the published
keys, and this launcher makes the family's bfloat16 weights on the device
in one jitted call.  Everything after that is ``main()``'s own wiring with
the flags of the configuration's ``engine`` block
(deploy/k8s-deploy-serve-http.yaml), the same for every family.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def memory_peak_bytes(jax, say) -> int:
    """The peak on the fullest chip: the allocator's peak of live arrays
    plus what it holds in reserve for the loaded programs' scratch.  On the
    TPU ``peak_bytes_in_use`` leaves the scratch out: before the ResNet-50
    step is loaded the reserve reads 0, from then on 4,508,532,736 bytes
    without change, where ``compiled.memory_analysis()`` gives the step
    4,545,677,312 bytes of temporaries; the live arrays' peak (0.58 GB) falls
    while the reserve is held, so the two add (PERF.md section 4)."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    say(f"memory_stats of the first device: {json.dumps(stats[0]) if stats else None}")
    return max((s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats), default=0)


def main(argv=None) -> None:
    t_start = time.monotonic()
    p = argparse.ArgumentParser(prog="chipbench-serve-child")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--platform", required=True, choices=["tpu", "cpu"])
    p.add_argument("--chips", type=int, required=True)
    p.add_argument("--warm", required=True, help="JSON: admission groups of [prompt_len, max_new]")
    p.add_argument("--fault", default="", help="tests only: break the timed path (alter_token)")
    args = p.parse_args(argv)
    with open(args.config) as f:
        conf = json.load(f)
    model, eng = conf, conf["engine"]  # the published keys sit at the top level

    import logging
    import random

    import jax

    # JAX's compile log, stamped: compiles_in_window counts the lines that
    # fall between the window's marks.
    logging.basicConfig(format="%(created).3f %(name)s %(message)s", stream=sys.stderr)

    from k8s_device_plugin_tpu.models import http_server as hs
    from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
    from k8s_device_plugin_tpu.models.engine_overload import OverloadConfig
    from k8s_device_plugin_tpu.utils import failpoints
    from k8s_device_plugin_tpu.utils import flight as flight_mod
    from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry
    from k8s_device_plugin_tpu.utils.platform import device_facts, enable_compilation_cache
    from k8s_device_plugin_tpu.utils.spans import SpanRecorder

    from . import families, weights

    say = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    # Every program is written to the cache, the sub-second ones too: a
    # second run in this checkout then compiles nothing.
    enable_compilation_cache(min_compile_seconds=0.0, log=say)
    facts = device_facts()
    say(f"backend: platform={facts['platform']} device_kind={facts['device_kind']!r} "
        f"device_count={facts['device_count']}")
    if facts["platform"] != args.platform or facts["device_count"] < args.chips:
        raise SystemExit(
            f"asked for {args.chips} x {args.platform}, JAX found "
            f"{facts['device_count']} x {facts['platform']}"
        )
    family = families.of(conf)
    cfg, paged = family.build(model, eng)
    t0 = time.monotonic()
    params = jax.jit(lambda words: family.params_tree(model, words))(weights.seed_words(args.seed))
    jax.block_until_ready(params)
    say(f"weights: {sum(x.size for x in jax.tree.leaves(params)) / 1e6:.1f} M parameters "
        f"in {time.monotonic() - t0:.1f} s")
    tp = int(eng.get("tp", 1))
    mesh = None
    if tp > 1:
        from k8s_device_plugin_tpu.parallel.mesh import mesh_from_allocation

        mesh = mesh_from_allocation(tp)
        say(f"tensor parallel: tp={tp} over {[str(d) for d in mesh.devices.flat]}")
    registry = MetricsRegistry()
    box = flight_mod.register(flight_mod.FlightRecorder(capacity=2048, name="engine"))
    failpoints.set_flight(box)
    engine = ServingEngine(
        cfg, params, paged,
        max_slots=eng["slots"],
        metrics=EngineMetrics(registry),
        spans=flight_mod.register_spans(SpanRecorder(capacity=512, name="engine")),
        flight=box,
        prefill_chunk=eng["prefill_chunk"],
        decode_block=eng["decode_block"],
        overlap_steps=1,
        admission=eng["admission"],
        overload=OverloadConfig(target_queue_wait_s=0.5, max_queue=512) if eng["overload"] else None,
        slo={"ttft_target_s": 2.0, "itl_p99_target_s": 0.25},
        kv_retain=bool(eng["kv_retain"]),
        kv_host_cache_mb=eng["kv_host_cache_mb"],
        role="unified",
        mesh=mesh,
    )
    del params
    # Warm-up, in admission groups the window may form (chipbench/run.py
    # warm_groups): engine.run submits a group at once, so one prefill job
    # of that size and bucket compiles, and each prompt length's graft.
    t0 = time.monotonic()
    rng = random.Random(f"chipbench-warm-{args.seed}")
    with open(args.warm) as f:
        groups = json.load(f)
    # With overload control on, submit() sheds a group whose projected wait
    # (queue over a drain rate learned from compile stalls) looks long;
    # the controller sits out the warm-up and meets the window unskewed.
    controller, engine.overload = engine.overload, None
    laps = []
    for group in groups:
        t_group = time.monotonic()
        done = engine.run([([rng.randrange(cfg.vocab_size) for _ in range(n)], new) for n, new in group])
        if not all(len(r.tokens) == new for r, (_, new) in zip(done, group)):
            raise SystemExit("warm-up: a request came back short")
        laps.append(f"{len(group)}x{max(n for n, _ in group)}:{time.monotonic() - t_group:.1f}")
    say("warm-up groups (size x longest prompt : seconds): " + " ".join(laps))
    engine.overload = controller
    say(f"warm-up: {len(groups)} groups, {sum(len(g) for g in groups)} requests in {time.monotonic() - t0:.1f} s")
    if args.fault == "alter_token":
        # tests/chipbench only: every token the engine emits is altered
        # where it is produced (the sampled id, before it is fed back).
        inner = engine._sample_first_token
        engine._sample_first_token = lambda req, logits: (inner(req, logits) + 1) % cfg.vocab_size
    watchdog = hs.StepWatchdog(lambda info: None, min_deadline_s=5.0, grace_deadline_s=120.0)
    chip_feed = None
    chip_paths = hs.visible_chip_paths()
    if chip_paths:
        chip_feed = hs.ChipHealthFeed(
            lambda info: None, url="", device_paths=chip_paths, poll_interval_s=1.0, flight=box,
        )
    server = hs.EngineServer(
        engine, port=0, registry=registry, enable_trace=True, enable_admin=True,
        watchdog=watchdog, chip_health=chip_feed,
    )
    server.start()

    def on_signal(signum, _frame):
        say(f"received {signal.Signals(signum).name}; draining")
        server.begin_drain(2.0)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, on_signal)
    say(f"child set-up {time.monotonic() - t_start:.1f} s")
    say(f"serving on :{server.port}")
    server.serve_forever()
    with open(os.path.join(args.run_dir, "serve_exit.json"), "w") as f:
        json.dump({
            "platform": facts["platform"], "kind": facts["device_kind"],
            "count": facts["device_count"], "memory_peak_bytes": memory_peak_bytes(jax, say),
        }, f)
    say("serve child: exit")


if __name__ == "__main__":
    main()
