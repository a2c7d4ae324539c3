"""In-pod benchmark runner — what the example/benchmark pods execute.

≙ the reference's benchmark container command (k8s-pod-example-gpu.yaml runs
convnet-benchmarks' `benchmark_alexnet.py` inside the pod).  Here the pod runs
    python -m k8s_device_plugin_tpu.models.benchmark --model resnet50 ...
against whatever chips the plugin allocated: the injected TPU_* env makes
libtpu expose exactly those chips, and the mesh axes are laid over them in
TPU_VISIBLE_CHIPS order so collectives ride the granted ICI block.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .alexnet import AlexNet
from .bert import Bert, BertConfig
from .data import synthetic_image_batch, synthetic_lm_batch, synthetic_token_batch
from .resnet import ResNet50
from .train import create_train_state, make_train_step
from ..parallel import distributed
from ..parallel.distributed import make_slice_mesh
from ..parallel.sharding import shard_train_step
from ..utils import tracing
from ..utils.platform import device_facts, enable_compilation_cache


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _positive_int(text: str) -> int:
    value = int(text)
    if value < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {value}")
    return value


def _sync(x):
    """Force completion via a device→host copy of ``x``.

    Checked on the v5e (chip run, PR 21): ``jax.block_until_ready`` IS a
    sync point on the real backend — twenty ResNet-50 b128 steps ending
    in it took 941.6 / 941.2 / 941.4 ms against 942.5 / 941.7 / 941.8 ms
    ending in ``device_get`` (the enqueue alone returned after ~50 ms),
    and a ``device_get`` issued after ``block_until_ready`` returned in
    under 1 ms.  Either ends a timed region honestly; this keeps the
    copy because every caller wants the value on the host anyway.
    """
    return jax.device_get(x)


def measure_two_point(run_small, run_big, n_delta: int, n_big: int):
    """Shared two-point timer for every benchmark in the repo.

    ``run_small``/``run_big`` are no-arg callables that execute one
    pre-compiled short/long program AND sync on its result (device_get).
    The short program runs twice: the spread between its two timings is a
    direct estimate of the dispatch/sync jitter, and the long-short delta
    only counts as signal when it clears 3x that jitter — keying the noise
    floor to measured jitter, not to a fraction of total runtime, so a
    small delta on top of a large constant part (e.g. long-prompt decode)
    is still trusted when the clock is steady.

    Returns (seconds attributed to the ``n_delta`` extra units, fell_back):
    on fallback the estimate is the long run scaled by ``n_delta/n_big`` —
    single-point, honest about including constant overhead.
    """
    times = []
    for fn in (run_small, run_small, run_big):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    t_small = min(times[0], times[1])
    jitter = abs(times[1] - times[0])
    dt = times[2] - t_small
    if dt <= 3 * jitter or dt <= 0:
        return times[2] * n_delta / max(n_big, 1), True
    return dt, False


def chained_tps(fn, short: int, full: int, label: str = "decode") -> float:
    """Units/sec from two whole-program lengths (the generate-bench shape).

    ``fn(n)`` must execute an n-unit program AND sync its result
    (device_get).  Warms/compiles both lengths, then two-point times them
    so constant prefill/dispatch cost cancels; on a below-noise-floor
    delta it logs and returns the scaled single-point estimate
    (overhead-diluted, but honest about it).  Shared by every bench that
    times a cached generate program (bench.py secondaries) so the
    warm/measure/fallback dance isn't re-cloned per bench.
    """
    fn(short)
    fn(full)
    dt, fell_back = measure_two_point(
        lambda: fn(short), lambda: fn(full), full - short, full
    )
    if fell_back:
        log(f"  ({label} delta below noise floor; single-point)")
    return (full - short) / dt


def multi_step(step, n: int):
    """Wrap ``step: (state, batch) -> (state, loss)`` into an ``n``-step
    `lax.fori_loop` — n training steps in ONE device dispatch: one traced
    program, no host round-trips between steps.  `fori_loop` with a
    carry-only body (no per-step stacked outputs) keeps the program's
    output buffers identical to a single step's.
    Returns ``(state, batch) -> (state, last_loss)``; jit at the call site.
    """

    def run(state, batch):
        # First step outside the loop pins the loss's shape/dtype for the
        # carry without guessing what the loss function returns.
        state, loss = step(state, batch)

        def body(_, carry):
            s, _ = carry
            return step(s, batch)

        return jax.lax.fori_loop(0, n - 1, body, (state, loss))

    return run


def timed_steps(step, state, batch, warmup: int, steps: int) -> tuple:
    """Two-point single-dispatch timing harness.

    AOT-compiles loop-of-step at two lengths (``warmup`` and
    ``warmup+steps``) and times one execution of each; the time difference
    covers exactly ``steps`` steps with the constant dispatch+sync overhead
    cancelled out.  ``warmup`` here sizes
    the short program — compilation is excluded by AOT, not by discarded
    runs.  Returns (state, loss, seconds_for_timed_steps); with
    ``small = max(1, warmup)`` the state advances ``3*small + steps`` steps
    (the short program runs twice to estimate timing jitter — see
    measure_two_point).
    """
    small = max(1, warmup)
    big = small + steps
    t0 = time.perf_counter()
    # AOT-compile both lengths up front (no execution): the timed calls
    # below are then first executions of ready executables — symmetric
    # constant overhead for both points, no compile inside the timed
    # region, and only small+big total steps executed (so the final
    # state/loss stay interpretable).
    run_small = jax.jit(multi_step(step, small), donate_argnums=0).lower(
        state, batch
    ).compile()
    run_big = jax.jit(multi_step(step, big), donate_argnums=0).lower(
        state, batch
    ).compile()
    log(f"compile {time.perf_counter() - t0:.1f}s")
    holder = {"state": state, "loss": None}

    def exec_small():
        holder["state"], holder["loss"] = run_small(holder["state"], batch)
        _sync(holder["loss"])

    def exec_big():
        holder["state"], holder["loss"] = run_big(holder["state"], batch)
        _sync(holder["loss"])

    dt, fell_back = measure_two_point(exec_small, exec_big, steps, big)
    if fell_back:
        log("two-point step delta below noise floor; reporting single-point")
    return holder["state"], holder["loss"], dt


def _gpt_config(args):
    from .transformer import GPTConfig

    if args.tiny:
        return GPTConfig.tiny()
    return GPTConfig(
        vocab_size=32000,
        hidden_size=1024,
        num_layers=8,
        num_heads=16,
        num_kv_heads=4,
        intermediate_size=2816,
        max_seq=max(args.seq_len, args.prompt_len + args.decode_tokens),
    )


def build(model_name: str, args, rng):
    if model_name == "alexnet":
        model = AlexNet(num_classes=1000, dtype=jnp.bfloat16)
        batch = synthetic_image_batch(rng, args.batch_size, args.image_size)
        return model, batch, "images", args.batch_size
    if model_name == "resnet50":
        model = ResNet50(
            num_classes=1000, dtype=jnp.bfloat16, stem=args.stem
        )
        batch = synthetic_image_batch(rng, args.batch_size, args.image_size)
        return model, batch, "images", args.batch_size
    if model_name == "vit":
        from .vit import ViT, ViTConfig

        if args.tiny:
            cfg = ViTConfig.tiny()
        else:
            # 256px/patch16 = 256 tokens — 128-aligned, so the encoder takes
            # the fused flash path end to end; --image-size overrides.
            cfg = ViTConfig(image_size=args.image_size if args.image_size != 224 else 256)
        model = ViT(cfg)
        batch = synthetic_image_batch(
            rng, args.batch_size, cfg.image_size, num_classes=cfg.num_classes
        )
        return model, batch, "images", args.batch_size
    if model_name == "bert":
        model = Bert(BertConfig.base())
        batch = synthetic_token_batch(rng, args.batch_size, args.seq_len)
        return model, batch, "input_ids", args.batch_size * args.seq_len
    if model_name == "gpt":
        from .transformer import TransformerLM

        cfg = _gpt_config(args)
        model = TransformerLM(cfg)
        batch = synthetic_lm_batch(rng, args.batch_size, args.seq_len, cfg.vocab_size)
        return model, batch, "input_ids", args.batch_size * args.seq_len
    raise SystemExit(f"unknown model {model_name!r}")


def checkpointed_steps(
    step, state, batch, target_steps: int, ckpt, every: int, warmup: int = 0
):
    """Train from the state's current step up to ``target_steps`` (absolute),
    saving asynchronously every ``every`` steps and once at the end.

    The first ``warmup`` steps run OUTSIDE the timed region (they absorb XLA
    compilation, like timed_steps' warmup) but are still real training steps
    — they advance ``state.step`` and participate in the checkpoint cadence,
    so resume arithmetic stays exact.  The final save is forced so a clean
    exit always leaves the latest step durable; mid-run kills lose at most
    ``every`` steps — the preemption contract the e2e test pins.

    Execution is chunked: the steps between two checkpoint boundaries run
    as ONE compiled scan (see `multi_step`), synced with a device_get only
    where a save needs the post-step state — so checkpoint cadence costs
    one host round-trip per save, not per step.
    Returns (state, last_loss | None, timed_seconds, steps_timed).
    """
    start = int(jax.device_get(state.step))
    warm_until = min(start + warmup, target_steps)
    # Absolute step numbers where the host must intervene: every checkpoint
    # boundary (s % every == 0, matching the reference cadence of saving
    # after step s), the warmup/timed split, and the end.
    bounds = sorted(
        {s for s in range(start + 1, target_steps + 1) if s % every == 0}
        | {warm_until, target_steps}
    )
    bounds = [b for b in bounds if b > start]
    # AOT-compile every distinct chunk length BEFORE any timer runs: a
    # chunk length first reached after warm_until would otherwise compile
    # inside the timed region and dominate dt with compile time.
    compiled: dict[int, object] = {}
    t0 = time.perf_counter()
    for a, b in zip([start] + bounds[:-1], bounds):
        n = b - a
        if n and n not in compiled:
            compiled[n] = jax.jit(multi_step(step, n), donate_argnums=0).lower(
                state, batch
            ).compile()
    if compiled:
        log(f"compile ({len(compiled)} chunk lengths) {time.perf_counter() - t0:.1f}s")

    def run_chunk(state, n):
        return compiled[n](state, batch)

    loss = None
    # warmup == 0 (or a resume landing past warm_until): everything is timed.
    t0 = time.perf_counter() if warm_until <= start < target_steps else None
    dt = 0.0
    cur = start
    for b in bounds:
        state, loss = run_chunk(state, b - cur)
        # Sync before saving so the saved state is the post-step one (and
        # so the timed region below measures execution, not queueing).
        _sync(loss)
        cur = b
        if b % every == 0:
            ckpt.save(state)
            log(f"checkpoint queued at step {b}")
        if b == warm_until and b != target_steps:
            t0 = time.perf_counter()
    if t0 is not None:
        dt = time.perf_counter() - t0
    # Final forced save — but not at a step that's already durable (a resumed
    # run that had nothing left to do would hit orbax's step-exists error).
    if int(jax.device_get(state.step)) != ckpt.latest_step():
        ckpt.save(state, force=True)
    ckpt.wait()
    return state, loss, dt, max(target_steps - warm_until, 0)


def run_decode(args) -> None:
    """Autoregressive decode throughput (tokens/sec) through the KV cache —
    the inference-side companion to the training benchmarks."""
    from .transformer import TransformerLM, greedy_generate, sample_generate

    cfg = _gpt_config(args)
    model = TransformerLM(cfg)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(
        rng, (args.batch_size, args.prompt_len), 0, cfg.vocab_size
    )
    params = model.init(rng, prompt)["params"]

    if args.temperature is not None:
        sample_rng = jax.random.PRNGKey(1)

        def greedy_generate(cfg, params, prompt, n):  # noqa: F811 — same timing path
            return sample_generate(
                cfg, params, prompt, n,
                rng=sample_rng, temperature=args.temperature, top_k=args.top_k,
            )

    # Two-point timing (see measure_two_point): a 1-new-token generate
    # covers the constant costs (dispatch and sync plus the bulk prefill
    # pass); the full generate adds exactly decode_tokens-1 more decode
    # steps, so the time difference is pure decode and the reported
    # tokens/sec is neither dispatch- nor prefill-diluted.  decode_tokens == 1
    # degenerates to single-point over all generated tokens incl. prefill.
    two_point = args.decode_tokens > 1
    full_steps = args.decode_tokens
    t0 = time.perf_counter()
    if two_point:
        _sync(greedy_generate(cfg, params, prompt, 1))
    _sync(greedy_generate(cfg, params, prompt, args.decode_tokens))
    log(f"decode compile+first run {time.perf_counter() - t0:.1f}s")
    with tracing.trace(args.trace_dir):
        if two_point:
            def exec_short():
                _sync(greedy_generate(cfg, params, prompt, 1))

            def exec_full():
                _sync(greedy_generate(cfg, params, prompt, args.decode_tokens))

            dt, fell_back = measure_two_point(
                exec_short, exec_full, args.decode_tokens - 1, full_steps
            )
            if fell_back:
                log("decode delta below noise floor; reporting single-point")
                two_point = False
                dt = dt * full_steps / (args.decode_tokens - 1)
        else:
            t0 = time.perf_counter()
            _sync(greedy_generate(cfg, params, prompt, args.decode_tokens))
            dt = time.perf_counter() - t0
    steps = args.decode_tokens - 1 if two_point else full_steps
    total_tokens = args.batch_size * steps
    print(
        json.dumps(
            {
                "model": "gpt-decode",
                "sampler": "greedy"
                if args.temperature is None
                else f"temperature={args.temperature},top_k={args.top_k}",
                **device_facts(),
                "chips": len(jax.devices()),
                "batch": args.batch_size,
                "prompt_len": args.prompt_len,
                "new_tokens": args.decode_tokens,
                "steps": steps,
                "throughput": round(total_tokens / dt, 2),
                "unit": "decoded tokens/sec (two-point, prefill+overhead excluded)"
                if two_point
                else "generated tokens/sec (incl. prefill cost)",
                "ms_per_token": round(dt / steps * 1e3, 3),
            }
        ),
        flush=True,
    )


def _run_router_phase(args) -> dict | None:
    """ROUTER perf phase: prefix-affinity routing vs a random-placement
    control over the SAME seeded multi-session traffic, against K real
    (tiny) serving replicas behind the router daemon.

    What the row claims and how it is measured:

    - **prefix-hit rate** — KV-tier hits (retained + host arena) summed
      across the replica engines per routed request.  Affinity keeps a
      session's shared prefix on one replica where the tiers revive it;
      random placement scatters it, so each replica keeps re-grafting.
      Engine counters, not router bookkeeping — the benefit is real KV
      work avoided.
    - **TTFT p99** — the router's own client-observed first-token
      histogram (tpu_router_ttft_seconds), warm, measured over the
      identical request sequence both times (same traffic seed).

    The replicas are deliberately tiny (GPTConfig.tiny) so the phase
    costs two small compiles, not two of the headline engines; both
    phases run over the SAME compiled replicas with KV tiers cleared
    in between, affinity first so any residual warmth favors the
    CONTROL.  Returns the JSON `router` block (None when disabled via
    --router-replicas 0)."""
    import dataclasses
    import os as _os
    import sys as _sys
    import threading

    from ..router.server import RouterServer
    from ..utils.metrics import MetricsRegistry
    from .engine import EngineMetrics, ServingEngine
    from .http_server import EngineServer
    from .transformer import GPTConfig, PagedConfig, TransformerLM

    n_replicas = getattr(args, "router_replicas", 2)
    if n_replicas < 2:
        return None
    # The multi-session replay lives with the chaos/sim harness
    # (tests/sim/traffic.py); the bench runs from the repo image, where
    # the repo root may or may not already be importable.
    try:
        from tests.sim.traffic import RouterTraffic
    except ImportError:
        _sys.path.insert(
            0,
            _os.path.dirname(
                _os.path.dirname(
                    _os.path.dirname(_os.path.abspath(__file__))
                )
            ),
        )
        from tests.sim.traffic import RouterTraffic

    page_size = 4
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    paged = PagedConfig(
        page_size=page_size, num_pages=64, max_pages_per_seq=16
    )
    rng = jax.random.PRNGKey(0)
    servers = []
    engines = []
    for i in range(n_replicas):
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(i), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg,
            params,
            paged,
            max_slots=4,
            metrics=EngineMetrics(registry),
            kv_retain=True,
            kv_host_cache_mb=16,
        )
        engines.append(engine)
        servers.append(
            EngineServer(
                engine, host="127.0.0.1", port=0, registry=registry
            ).start()
        )

    def _post_replica(port, prompt, max_new):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": max_new}
            ).encode(),
            method="POST",
        )
        urllib.request.urlopen(req, timeout=120).read()

    # Warmup EVERY replica over the (batch, bucket) prefill grid the
    # replay can hit (prefix 16 + suffix <= 4 tokens -> one bucket;
    # concurrent admissions batch up to the client concurrency), so no
    # XLA compile lands inside either measured pass — and neither
    # policy's pass eats a compile the other skipped.
    for server in servers:
        for group in (1, 2, 3, 4):
            threads = [
                threading.Thread(
                    target=_post_replica,
                    args=(server.port, [7 + g] * 18, 6),
                )
                for g in range(group)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

    replica_names = [f"127.0.0.1:{s.port}" for s in servers]
    # More sessions than replicas: every session random placement
    # scatters pays a cold prefix graft per EXTRA replica it touches,
    # while affinity pays exactly one per session — the gap the
    # hit-rate columns exist to show.
    sessions, prefix_len, n_requests = 8, 16, 32

    def _kv_hits():
        return sum(e.kv_retained_hits + e.kv_host_hits for e in engines)

    def _measure(mode):
        router = RouterServer(
            replica_names,
            host="127.0.0.1",
            port=0,
            # One prefix block = one KV page of the tiny replicas; four
            # blocks = exactly the shared session prefix.
            prefix_block_tokens=page_size,
            prefix_max_blocks=prefix_len // page_size,
            poll_interval_s=0.2,
            hedge=False,
            policy_mode=mode,
            seed=3,
        ).start()
        traffic = RouterTraffic(
            "127.0.0.1",
            router.port,
            seed=17,
            sessions=sessions,
            prefix_len=prefix_len,
            vocab=cfg.vocab_size,
        )
        # Warm pass (same seed as the measured pass: identical shapes),
        # then clear every KV tier so the measurement starts cold.
        traffic.run(
            n_requests, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        for engine in engines:
            engine.kvcache_clear()
        hits0 = _kv_hits()
        ttft_snap = router.metrics.ttft_seconds.snapshot()
        report = traffic.run(
            n_requests, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        placements = {
            key: router.metrics.placements.value(placement=key)
            for key in ("home", "overflow", "random", "failover")
        }
        out = {
            "prefix_hits": _kv_hits() - hits0,
            "hit_rate": round((_kv_hits() - hits0) / n_requests, 3),
            "ttft_p99_ms": (
                None
                if (
                    q := router.metrics.ttft_seconds.quantile(
                        0.99, since=ttft_snap
                    )
                )
                is None
                else round(q * 1e3, 3)
            ),
            "home_rate": round(
                placements["home"] / max(1, sum(placements.values())), 3
            ),
            "dropped": report.dropped,
            "failovers": int(router.metrics.failovers.value()),
            "retries": int(router.metrics.retries.value()),
        }
        router.stop()
        return out

    # Affinity FIRST: any residual warmth then biases toward the
    # random CONTROL, never for the claim.
    affinity = _measure("affinity")
    random_ctl = _measure("random")
    for server in servers:
        server.stop()
    block = {
        "replicas": n_replicas,
        "requests": n_requests,
        "sessions": sessions,
        "affinity": affinity,
        "random": random_ctl,
    }
    log(
        "perf-ledger row: | ROUTER prefix-affinity (K=%d, %d sessions) | "
        "affinity %.2f KV hits/req, TTFT p99 %s ms (home rate %.2f) vs "
        "random %.2f hits/req, %s ms | - | `benchmark.py --model serving` "
        "| update on bench round |"
        % (
            n_replicas,
            sessions,
            affinity["hit_rate"],
            affinity["ttft_p99_ms"],
            affinity["home_rate"],
            random_ctl["hit_rate"],
            random_ctl["ttft_p99_ms"],
        )
    )
    return block


def _run_fabric_phase(args) -> dict | None:
    """FABRIC perf phase: the fleet-wide content-addressed KV fabric
    (router/fabric.py, ISSUE 18) vs an affinity-only control over the
    SAME seeded traffic in which every session opens with one SHARED
    system prompt.

    What the row claims and how it is measured:

    - **fleet hits/request** — with the fabric on, the shared prefix is
      prefilled ONCE fleet-wide: the first replica to hold it advertises
      a bloom digest, the router's locator stamps it as the handoff
      source on every dial whose target lacks the prefix, and the target
      pulls the pages instead of recomputing them.  Engine KV-tier hits
      (retained + host arena) per request must be strictly ABOVE the
      affinity-only control, where each replica pays its own cold
      prefill of the very same system prompt.  bench_diff screams
      NO-FABRIC-HITS when the cross-peer pull count is zero.
    - **TTFT p99** — the router's client-observed histogram over the
      identical sequence; the pulls must not cost latency (bench_diff
      screams FABRIC-TTFT-REGRESSED past 1.2x the control).

    The fabric pass runs FIRST so residual warmth favors the CONTROL;
    the control pass sleeps the same locator-settle time the fabric
    pass measured, so neither side gets a free warm-up.  Returns the
    JSON ``fabric`` block (None when multi-replica phases are disabled
    via --router-replicas < 2)."""
    import dataclasses
    import os as _os
    import sys as _sys
    import threading
    import time as _time

    from ..router.fabric import FabricConfig
    from ..router.server import RouterServer
    from ..utils.metrics import MetricsRegistry
    from .engine import EngineMetrics, ServingEngine
    from .http_server import EngineServer
    from .transformer import GPTConfig, PagedConfig, TransformerLM

    if getattr(args, "router_replicas", 2) < 2:
        return None
    # Fleet-wide dedup is only interesting past two replicas: with
    # three, affinity alone CANNOT keep the shared prompt hot
    # everywhere, so the control pays the recompute the fabric avoids.
    n_replicas = max(3, getattr(args, "router_replicas", 2))
    try:
        from tests.sim.traffic import RouterTraffic
    except ImportError:
        _sys.path.insert(
            0,
            _os.path.dirname(
                _os.path.dirname(
                    _os.path.dirname(_os.path.abspath(__file__))
                )
            ),
        )
        from tests.sim.traffic import RouterTraffic

    page_size = 4
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    paged = PagedConfig(
        page_size=page_size, num_pages=64, max_pages_per_seq=16
    )
    servers = []
    engines = []
    # IDENTICAL weights on every replica — a real fleet serves one
    # model, and the handoff fingerprint check rightly refuses KV
    # pulled across different params.
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    for i in range(n_replicas):
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg,
            params,
            paged,
            max_slots=4,
            metrics=EngineMetrics(registry),
            kv_retain=True,
            kv_host_cache_mb=16,
        )
        engines.append(engine)
        servers.append(
            EngineServer(
                engine, host="127.0.0.1", port=0, registry=registry
            ).start()
        )

    def _post_replica(port, prompt, max_new):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": max_new}
            ).encode(),
            method="POST",
        )
        urllib.request.urlopen(req, timeout=120).read()

    # Warmup every replica over the (batch, bucket) grid the replay can
    # hit (shared 16 + unique 16 + suffix <= 4 tokens; admissions batch
    # up to the client concurrency) so no XLA compile lands inside a
    # measured pass.
    for server in servers:
        for group in (1, 2, 3, 4):
            threads = [
                threading.Thread(
                    target=_post_replica,
                    args=(server.port, [7 + g] * 36, 6),
                )
                for g in range(group)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

    replica_names = [f"127.0.0.1:{s.port}" for s in servers]
    # Every session shares the same 16-token system prompt but keeps a
    # 16-token unique tail, so affinity homes SESSIONS apart while the
    # fabric dedups the shared HEAD across those homes.
    sessions, prefix_len, shared_len, n_requests = 8, 32, 16, 32

    def _kv_hits():
        return sum(e.kv_retained_hits + e.kv_host_hits for e in engines)

    def _pulls():
        return sum(e.handoff_fetches for e in engines)

    def _measure(use_fabric, settle_s):
        router = RouterServer(
            replica_names,
            host="127.0.0.1",
            port=0,
            prefix_block_tokens=page_size,
            prefix_max_blocks=prefix_len // page_size,
            poll_interval_s=0.2,
            hedge=False,
            policy_mode="affinity",
            seed=3,
            fabric=use_fabric,
            fabric_config=FabricConfig(default_page_size=page_size),
        ).start()
        traffic = RouterTraffic(
            "127.0.0.1",
            router.port,
            seed=17,
            sessions=sessions,
            prefix_len=prefix_len,
            shared_prefix_len=shared_len,
            vocab=cfg.vocab_size,
        )
        # Warm pass (identical shapes), then clear every KV tier so the
        # measurement starts cold on every replica.
        traffic.run(
            n_requests, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        for engine in engines:
            engine.kvcache_clear()
        # Seed ONE owner with the shared system prompt (through the
        # router, so affinity picks the home it would in production),
        # then give the locator time to see the cleared digests and the
        # new owner's advertisement.  The control pass sleeps the SAME
        # measured settle so TTFT is compared apples to apples.
        t0 = _time.monotonic()
        _post_replica(router.port, traffic.prefixes[0][:shared_len], 4)
        if use_fabric:
            # Right after the clear the locator still holds PRE-clear
            # views (every replica nonzero) for up to a poll tick —
            # settled means the refreshed truth: exactly the seed
            # owner advertises, everyone else reads empty.
            deadline = _time.monotonic() + 10.0
            while _time.monotonic() < deadline:
                roots = router.fabric.advertised_roots()
                if sum(1 for v in roots.values() if v) == 1:
                    break
                _time.sleep(0.05)
            settle_s = _time.monotonic() - t0
        else:
            _time.sleep(max(0.0, settle_s - (_time.monotonic() - t0)))
        hits0 = _kv_hits()
        pulls0 = _pulls()
        ttft_snap = router.metrics.ttft_seconds.snapshot()
        report = traffic.run(
            n_requests, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        out = {
            "fleet_hits": _kv_hits() - hits0,
            "hit_rate": round((_kv_hits() - hits0) / n_requests, 3),
            "ttft_p99_ms": (
                None
                if (
                    q := router.metrics.ttft_seconds.quantile(
                        0.99, since=ttft_snap
                    )
                )
                is None
                else round(q * 1e3, 3)
            ),
            "cross_peer_pulls": _pulls() - pulls0,
            "dropped": report.dropped,
        }
        router.stop()
        return out, settle_s

    # Fabric FIRST: any residual warmth then biases toward the
    # affinity-only CONTROL, never for the claim.
    fabric_run, settle_s = _measure(True, 0.0)
    control, _ = _measure(False, settle_s)
    for server in servers:
        server.stop()
    block = {
        "replicas": n_replicas,
        "requests": n_requests,
        "sessions": sessions,
        "shared_prefix_len": shared_len,
        "fabric": fabric_run,
        "control": control,
    }
    log(
        "perf-ledger row: | FABRIC fleet KV (K=%d, %d sessions, shared "
        "%d) | fabric %.2f KV hits/req, TTFT p99 %s ms, %d cross-peer "
        "pulls vs control %.2f hits/req, %s ms | - | `benchmark.py "
        "--model serving` | update on bench round |"
        % (
            n_replicas,
            sessions,
            shared_len,
            fabric_run["hit_rate"],
            fabric_run["ttft_p99_ms"],
            fabric_run["cross_peer_pulls"],
            control["hit_rate"],
            control["ttft_p99_ms"],
        )
    )
    return block


def _run_canary_phase(args) -> dict | None:
    """CANARY perf phase: the active correctness plane's overhead and
    detection self-check (router/prober.py, ISSUE 17).

    What the row claims and how it is measured:

    - **overhead** — serving throughput (client-observed tokens/sec
      through the router over the SAME seeded traffic) with the canary
      prober running at an aggressive interval vs with it off, against
      real (tiny) serving replicas.  The prober-ON pass runs FIRST so
      any residual warmth favors the OFF control — the overhead number
      is conservative.  bench_diff screams PROBE-OVERHEAD past 1%.
    - **mismatch_detected / fences** — the detection self-check: after
      the measured passes, the ``engine.readback=corrupt`` failpoint
      (docs/chaos.md) flips one token byte in every readback; the
      prober MUST verdict mismatch within a few sweeps and auto-fence.
      bench_diff screams MISMATCH-MISSED when this flips false — a
      blind detector is the worst possible correctness-plane
      regression, and nothing else would say so.

    Returns the JSON ``canary`` block (None when the router phase is
    disabled via --router-replicas < 2 — same replicas budget)."""
    import dataclasses
    import os as _os
    import sys as _sys
    import threading
    import time as _time

    from ..router.prober import CanaryConfig
    from ..router.server import RouterServer
    from ..utils import failpoints
    from ..utils.metrics import MetricsRegistry
    from .engine import EngineMetrics, ServingEngine
    from .http_server import EngineServer
    from .transformer import GPTConfig, PagedConfig, TransformerLM

    if getattr(args, "router_replicas", 2) < 2:
        return None
    try:
        from tests.sim.traffic import RouterTraffic
    except ImportError:
        _sys.path.insert(
            0,
            _os.path.dirname(
                _os.path.dirname(
                    _os.path.dirname(_os.path.abspath(__file__))
                )
            ),
        )
        from tests.sim.traffic import RouterTraffic

    page_size = 4
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    paged = PagedConfig(
        page_size=page_size, num_pages=64, max_pages_per_seq=16
    )
    servers = []
    for i in range(2):
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(100 + i), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg,
            params,
            paged,
            max_slots=4,
            metrics=EngineMetrics(registry),
        )
        servers.append(
            EngineServer(
                engine,
                host="127.0.0.1",
                port=0,
                registry=registry,
                enable_admin=True,  # the prober's auto-fence target
            ).start()
        )

    def _post_replica(port, prompt, max_new):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": max_new}
            ).encode(),
            method="POST",
        )
        urllib.request.urlopen(req, timeout=120).read()

    # Warm every (batch, bucket) shape BOTH the traffic replay and the
    # canary probes can hit, so no XLA compile lands inside either
    # measured pass (the probe prompt is tiny — its bucket too).
    for server in servers:
        for group in (1, 2, 3, 4):
            threads = [
                threading.Thread(
                    target=_post_replica,
                    args=(server.port, [7 + g] * 18, 6),
                )
                for g in range(group)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        _post_replica(server.port, [11, 13, 17, 19], 4)

    replica_names = [f"127.0.0.1:{s.port}" for s in servers]
    canary_cfg = CanaryConfig(
        interval_s=0.25,  # far hotter than production: worst case
        probe_tokens=4,
        prompts=((11, 13, 17, 19),),
        k_mismatch=2,
        fence=True,
    )

    def _measure(canary_on):
        router = RouterServer(
            replica_names,
            host="127.0.0.1",
            port=0,
            prefix_block_tokens=page_size,
            prefix_max_blocks=4,
            poll_interval_s=0.2,
            hedge=False,
            seed=3,
            canary=canary_on,
            canary_config=canary_cfg,
        ).start()
        traffic = RouterTraffic(
            "127.0.0.1",
            router.port,
            seed=23,
            sessions=4,
            prefix_len=16,
            vocab=cfg.vocab_size,
        )
        # Warm pass, then the measured pass over identical shapes.
        traffic.run(8, concurrency=4, suffix_len=(1, 4), max_new=(4, 8))
        report = traffic.run(
            24, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        tps = report.tokens / max(report.duration_s, 1e-9)
        return router, tps, report

    # Prober ON first: residual warmth then favors the OFF control,
    # never the claim.
    router_on, tps_on, report_on = _measure(True)
    probes = sum(
        row["probes"]
        for row in router_on.prober.snapshot()["replicas"].values()
    )

    # Detection self-check on the still-running canary router: corrupt
    # every readback, wait for mismatch -> auto-fence.
    failpoints.arm_spec("engine.readback=corrupt")
    mismatch_detected = False
    fences = 0
    try:
        deadline = _time.monotonic() + 15.0
        while _time.monotonic() < deadline:
            snap = router_on.prober.snapshot()
            fences = snap["fences_fired"]
            if fences >= 1:
                mismatch_detected = True
                break
            _time.sleep(0.1)
    finally:
        failpoints.disarm("engine.readback")
    router_on.stop()
    for server in servers:
        server.unfence()

    router_off, tps_off, report_off = _measure(False)
    router_off.stop()
    for server in servers:
        server.stop()

    overhead = max(0.0, 1.0 - tps_on / tps_off) if tps_off else None
    block = {
        "replicas": 2,
        "interval_s": canary_cfg.interval_s,
        "tokens_per_sec_canary": round(tps_on, 2),
        "tokens_per_sec_control": round(tps_off, 2),
        "overhead": round(overhead, 4) if overhead is not None else None,
        "probes": probes,
        "dropped": report_on.dropped + report_off.dropped,
        "mismatch_detected": mismatch_detected,
        "fences": fences,
    }
    log(
        "perf-ledger row: | CANARY active probing (interval %.2fs) | "
        "overhead %s (%.2f vs %.2f tokens/sec, %d probes); injected "
        "corruption %s (%d fences) | - | `benchmark.py --model serving` "
        "| update on bench round |"
        % (
            canary_cfg.interval_s,
            block["overhead"],
            tps_on,
            tps_off,
            probes,
            "detected+fenced" if mismatch_detected else "MISSED",
            fences,
        )
    )
    return block


def _run_postmortem_phase(args) -> dict | None:
    """POSTMORTEM perf phase: black-box archaeology overhead and the
    capture/classification self-check (router/postmortem.py +
    tools/postmortem.py, ISSUE 20).

    What the row claims and how it is measured:

    - **overhead** — serving throughput (client-observed tokens/sec
      through the router over the SAME seeded traffic) with the fleet
      postmortem collector armed vs off, against real (tiny) serving
      replicas.  The armed pass runs FIRST so residual warmth favors
      the control — the overhead number is conservative.  bench_diff
      screams CAPTURE-OVERHEAD past 1%.
    - **bundle_found / root_cause** — the archaeology self-check: after
      the measured passes, a watchdog-source fence incident is injected
      on one replica; the summary-poll incident cursor must fire
      exactly one fleet bundle, and ``tools/postmortem.py`` must
      classify the ON-DISK bundle ``watchdog_hang``.  bench_diff
      screams CAPTURE-MISSED when no bundle lands and ROOTCAUSE-WRONG
      on a misclassification — a capture plane that misses or
      misattributes incidents is worse than none (operators trust it).

    Returns the JSON ``postmortem`` block (None when the router phase
    is disabled via --router-replicas < 2 — same replicas budget)."""
    import dataclasses
    import importlib.util
    import os as _os
    import shutil as _shutil
    import sys as _sys
    import tempfile as _tempfile
    import threading
    import time as _time

    from ..router.server import RouterServer
    from ..utils.metrics import MetricsRegistry
    from .engine import EngineMetrics, ServingEngine
    from .http_server import EngineServer
    from .transformer import GPTConfig, PagedConfig, TransformerLM

    if getattr(args, "router_replicas", 2) < 2:
        return None
    repo_root = _os.path.dirname(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    )
    try:
        from tests.sim.traffic import RouterTraffic
    except ImportError:
        _sys.path.insert(0, repo_root)
        from tests.sim.traffic import RouterTraffic

    spec = importlib.util.spec_from_file_location(
        "postmortem_tool", _os.path.join(repo_root, "tools", "postmortem.py")
    )
    pm_tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm_tool)

    page_size = 4
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    paged = PagedConfig(
        page_size=page_size, num_pages=64, max_pages_per_seq=16
    )
    servers = []
    for i in range(2):
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(200 + i), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg,
            params,
            paged,
            max_slots=4,
            metrics=EngineMetrics(registry),
        )
        servers.append(
            EngineServer(
                engine, host="127.0.0.1", port=0, registry=registry
            ).start()
        )

    def _post_replica(port, prompt, max_new):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompt": prompt, "max_new_tokens": max_new}
            ).encode(),
            method="POST",
        )
        urllib.request.urlopen(req, timeout=120).read()

    # Warm every (batch, bucket) shape the traffic replay can hit, so
    # no XLA compile lands inside either measured pass.
    for server in servers:
        for group in (1, 2, 3, 4):
            threads = [
                threading.Thread(
                    target=_post_replica,
                    args=(server.port, [7 + g] * 18, 6),
                )
                for g in range(group)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

    replica_names = [f"127.0.0.1:{s.port}" for s in servers]
    dump_dir = _tempfile.mkdtemp(prefix="bench-postmortem-")

    def _measure(postmortem_on):
        router = RouterServer(
            replica_names,
            host="127.0.0.1",
            port=0,
            prefix_block_tokens=page_size,
            prefix_max_blocks=4,
            poll_interval_s=0.2,
            hedge=False,
            seed=3,
            postmortem=postmortem_on,
            postmortem_dir=dump_dir,
        ).start()
        traffic = RouterTraffic(
            "127.0.0.1",
            router.port,
            seed=29,
            sessions=4,
            prefix_len=16,
            vocab=cfg.vocab_size,
        )
        # Warm pass, then the measured pass over identical shapes.
        traffic.run(8, concurrency=4, suffix_len=(1, 4), max_new=(4, 8))
        report = traffic.run(
            24, concurrency=4, suffix_len=(1, 4), max_new=(4, 8)
        )
        tps = report.tokens / max(report.duration_s, 1e-9)
        return router, tps, report

    # Collector ON first: residual warmth then favors the OFF control,
    # never the claim.
    router_on, tps_on, report_on = _measure(True)

    # Archaeology self-check on the still-running armed router: a
    # watchdog-source fence incident on replica 0 (the flight event +
    # discrete incident the real hung-step watchdog emits) must ride
    # the summary-poll cursor into ONE fleet bundle that classifies as
    # watchdog_hang FROM DISK.
    victim = servers[0]
    victim.engine.flight.record(
        "engine.fenced", reason="hung_step", source="watchdog"
    )
    victim.engine.anomaly.report(
        "engine.fenced", reason="hung_step", source="watchdog"
    )
    bundle_found = False
    root_cause = None
    deadline = _time.monotonic() + 20.0
    while _time.monotonic() < deadline:
        if router_on.postmortem.captures >= 1:
            bundle_found = True
            break
        _time.sleep(0.1)
    captures = router_on.postmortem.captures
    if bundle_found:
        bundle_path = router_on.postmortem.last_bundle
        loaded = pm_tool.load_bundle(bundle_path)
        timeline = pm_tool.build_timeline(loaded["components"])
        root_cause = pm_tool.classify(timeline)["root_cause"]
    router_on.stop()

    router_off, tps_off, report_off = _measure(False)
    router_off.stop()
    for server in servers:
        server.stop()
    _shutil.rmtree(dump_dir, ignore_errors=True)

    overhead = max(0.0, 1.0 - tps_on / tps_off) if tps_off else None
    rootcause_ok = root_cause == "watchdog_hang"
    block = {
        "replicas": 2,
        "tokens_per_sec_postmortem": round(tps_on, 2),
        "tokens_per_sec_control": round(tps_off, 2),
        "overhead": round(overhead, 4) if overhead is not None else None,
        "dropped": report_on.dropped + report_off.dropped,
        "captures": captures,
        "bundle_found": bundle_found,
        "root_cause": root_cause,
        "rootcause_ok": rootcause_ok,
    }
    log(
        "perf-ledger row: | POSTMORTEM fleet capture | overhead %s "
        "(%.2f vs %.2f tokens/sec); injected watchdog fence %s "
        "(%d bundles, classified %s) | - | `benchmark.py --model "
        "serving` | update on bench round |"
        % (
            block["overhead"],
            tps_on,
            tps_off,
            "captured" if bundle_found else "MISSED",
            captures,
            root_cause if rootcause_ok else f"WRONG ({root_cause})",
        )
    )
    return block


def _run_autoscale_phase(args) -> dict:
    """AUTOSCALE perf phase: the closed-loop fleet controller
    (controller/reconciler.py — the REAL Reconciler + FleetSimActuator,
    fake clock) vs a static peak-provisioned fleet over the SAME
    deterministic 600-sim-second diurnal + flash-crowd demand trace.

    What the row claims and how it is measured:

    - **replica-minutes** — both fleets' bills over the identical
      trace, from the controller's own accrual ledger (serving AND
      still-warming replicas are billed; the elastic fleet must come
      in STRICTLY under the static fleet sized for the observed peak,
      or the autoscaler is not paying for itself).
    - **TTFT p99 / SLO violations** — a fluid-queue fleet model: one
      global backlog drained at ``cap_rps`` per serving replica, plus
      an M/M/1-flavored in-service wait term so a keeping-up-but-busy
      fleet reports nonzero pressure (utilization separates busy from
      idle without a backlog — without that term the model flaps:
      every drain-to-empty reads as cold, every reap re-hots the
      fleet).  TTFT = base + queue wait; a sim-second above ``slo_ms``
      is a violation, and the controller fleet must log ZERO.

    The demand trace, thresholds, and clock are all deterministic (no
    RNG, no wall time), so the block's numbers are exactly reproducible
    and tools/bench_diff.py can gate on them (REPLICA-MINUTES-REGRESSED
    / AUTOSCALE-SLO-VIOLATED).  Pure host-side Python: no compiles, no
    devices, ~milliseconds of wall clock."""
    import math

    from ..controller import (
        ControllerConfig,
        FleetSimActuator,
        Reconciler,
    )
    from ..router.migration import scale_recommendation

    sim_seconds = 600
    cap_rps = 40.0  # one replica's drain rate
    base_ttft_ms = 60.0
    slo_ms = 2500.0  # TTFT budget: base + queue wait
    hot_wait_s, cold_wait_s = 0.2, 0.02
    warm_lag_s = 3.0  # spawn -> serving (peer-warmed join)

    def demand(t: float) -> float:
        """Diurnal sinusoid (5-minute "day", 15..75 rps) with a flash
        crowd riding the second peak: +80 rps ramping in over 30s,
        holding 60s, ramping out."""
        diurnal = 45.0 + 30.0 * math.sin(
            2 * math.pi * (t - 225.0) / 300.0
        )
        if 300 <= t < 330:
            flash = 80.0 * (t - 300) / 30.0
        elif 330 <= t < 390:
            flash = 80.0
        elif 390 <= t < 420:
            flash = 80.0 * (420 - t) / 30.0
        else:
            flash = 0.0
        return max(0.0, diurnal + flash)

    class _Sim:
        """Deterministic fluid-queue fleet: the actuator seam mutates
        it, the fleet() view is what the controller polls."""

        def __init__(self, n0: int):
            self.n = n0
            self.names = [f"sim-{i}" for i in range(n0)]
            self.counter = n0
            self.warming: list = []  # [ready_at, name]
            self.queue = 0.0
            self.t = 0.0
            self.ttfts_ms: list = []
            self.violations = 0
            self.replica_seconds = 0.0
            self.peak = n0

        # ----- actuator verbs (FleetSimActuator closures) -----------
        def spawn(self, role: str) -> str:
            name = f"sim-{self.counter}"
            self.counter += 1
            self.warming.append([self.t + warm_lag_s, name])
            return name

        def reap(self, name: str) -> None:
            if name in self.names:
                self.names.remove(name)
                self.n -= 1

        # ----- signal model -----------------------------------------
        def wait_s(self, d: float) -> float:
            # rho capped below 1: past saturation the backlog term
            # carries the overload signal (uncapped, the M/M/1 term
            # diverges and reports a 25s wait over an empty queue).
            rho = min(0.98, d / (self.n * cap_rps))
            return (
                self.queue / (self.n * cap_rps)
                + rho / (1.0 - rho) / cap_rps
            )

        # ----- one sim second ---------------------------------------
        def step(self) -> None:
            for entry in [w for w in self.warming if w[0] <= self.t]:
                self.warming.remove(entry)
                self.names.append(entry[1])
                self.n += 1
            d = demand(self.t)
            self.queue = max(0.0, self.queue + d - self.n * cap_rps)
            ttft = base_ttft_ms + self.wait_s(d) * 1000.0
            self.ttfts_ms.append(ttft)
            self.violations += ttft > slo_ms
            self.replica_seconds += self.n + len(self.warming)
            self.peak = max(self.peak, self.n + len(self.warming))
            self.t += 1.0

        # ----- the /debug/fleet shape the controller polls ----------
        def fleet(self) -> dict:
            wait = round(self.wait_s(demand(self.t)), 4)
            per_q = int(self.queue / self.n)
            rows = {
                name: {
                    "role": "unified",
                    "pressure_s": wait,
                    "queue_depth": per_q,
                    "eligible": True,
                    "reachable": True,
                    "draining": False,
                    "fenced": False,
                }
                for name in self.names
            }
            # Warming joiners: visible (and billed) but ineligible, so
            # they neither read as cold headroom nor get reaped.
            for _, name in self.warming:
                rows[name] = {
                    "role": "unified",
                    "pressure_s": 0.0,
                    "queue_depth": 0,
                    "eligible": False,
                    "reachable": True,
                    "draining": False,
                    "fenced": False,
                }
            return {
                "replicas": rows,
                "recommendation": scale_recommendation(
                    rows,
                    hot_wait_s=hot_wait_s,
                    cold_wait_s=cold_wait_s,
                ),
            }

    static_n = max(
        math.ceil(demand(t) / cap_rps) for t in range(sim_seconds)
    )

    sim = _Sim(2)
    actuator = FleetSimActuator(
        spawn_fn=sim.spawn,
        join_fn=lambda name, role: None,  # joins when warm_lag elapses
        drain_fn=lambda name: None,  # cold pool: nothing in flight
        reap_fn=sim.reap,
        warm_fn=lambda name, donor: None,  # lag above IS the transfer
    )
    rc = Reconciler(
        sim.fleet,
        actuator,
        config=ControllerConfig(
            interval_s=2.0,
            sustain_ticks=2,
            cooldown_s=10.0,
            min_replicas=1,
            max_replicas=12,
            hot_wait_s=hot_wait_s,
            cold_wait_s=cold_wait_s,
        ),
        now=lambda: sim.t,
    )
    for s in range(sim_seconds):
        if s % 2 == 0:
            rc.tick()
        sim.step()

    static = _Sim(static_n)
    for _ in range(sim_seconds):
        static.step()

    def _p99(xs: list) -> float:
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * 0.99))]

    ctrl_minutes = round(sim.replica_seconds / 60.0, 2)
    static_minutes = round(static.replica_seconds / 60.0, 2)
    block = {
        "sim_seconds": sim_seconds,
        "slo_ms": slo_ms,
        "controller": {
            "replica_minutes": ctrl_minutes,
            "ttft_p99_ms": round(_p99(sim.ttfts_ms), 1),
            "slo_violations": sim.violations,
            "peak_replicas": sim.peak,
            "scale_ups": rc.scale_ups,
            "scale_downs": rc.scale_downs,
            "role_flips": rc.role_flips,
            "actions": rc.actions_executed,
        },
        "static_peak": {
            "replicas": static_n,
            "replica_minutes": static_minutes,
            "ttft_p99_ms": round(_p99(static.ttfts_ms), 1),
            "slo_violations": static.violations,
        },
        "replica_minutes_saved": (
            round(1.0 - ctrl_minutes / static_minutes, 3)
            if static_minutes
            else None
        ),
    }
    log(
        "perf-ledger row: | AUTOSCALE closed-loop controller (%ds "
        "diurnal+flash sim) | replica-minutes %.1f vs static-peak %.1f "
        "(%.0f%% saved); ttft p99 %.0fms vs %.0fms (slo %.0fms, "
        "violations %d vs %d); %d actions (%d up, %d down) | - | "
        "`benchmark.py --model serving` | update on bench round |"
        % (
            sim_seconds,
            ctrl_minutes,
            static_minutes,
            100.0 * (block["replica_minutes_saved"] or 0.0),
            block["controller"]["ttft_p99_ms"],
            block["static_peak"]["ttft_p99_ms"],
            slo_ms,
            sim.violations,
            static.violations,
            rc.actions_executed,
            rc.scale_ups,
            rc.scale_downs,
        )
    )
    return block


def _run_kernels_phase(args) -> dict | None:
    """KERNELS perf phase: the split-K paged-attention kernel vs the
    engine's gather fallback vs the old single-pass Pallas path, per
    shape x KV format — the per-shape kernel perf ledger that
    tools/bench_diff.py gates regressions against.

    What the row claims and how it is measured:

    - **kernel** — `ops.paged_attention` through its default routing
      (compiled Mosaic split-K on TPU; the vectorized XLA
      implementation of the same split math on CPU — the route the
      engine's decode step actually takes), split degree from the
      per-generation tuning table (ops/tuning.py).
    - **gather** — the engine's fallback math verbatim
      (models/transformer.py: materialize the [max_len] view,
      dequantize it when quantized, masked grouped einsum).
    - **single** — the pre-split-K kernel shape: `num_splits=1` forced
      through the Pallas lane (the interpreter on CPU — exactly what
      the r03–r05 smoke rows measured at 0.06–0.12x of gather; the
      compiled 1-split kernel on TPU).

    Every arm runs the SAME jitted-callable discipline (warm twice,
    min-of-N timed executions, device_get sync), and the quantized
    shapes share the bf16 shape's geometry so the `int8_vs_bf16` field
    is a like-for-like fused-dequant claim.  Returns the JSON `kernels`
    block (None when skipped via `--no-kernel`)."""
    if not getattr(args, "kernel", True):
        return None
    from ..ops import tuning
    from ..ops.paged_attention import paged_attention
    from ..ops.quant import (
        dequantize_kv,
        dequantize_kv4,
        quantize_kv,
        quantize_kv4,
    )

    # (name, batch, heads, kv_heads, head_dim, page_size, pages, fill, fmt)
    # — the CPU smoke set: one moderate GQA shape per format plus a
    # longer MQA context where the split axis has real work.  fill < 1
    # leaves a partial frontier page (the masked-tail case).
    shapes = [
        ("b4_gqa_f32", 4, 8, 4, 64, 16, 8, 0.75, "f32"),
        ("b2_mqa_long_f32", 2, 16, 2, 64, 16, 32, 0.4, "f32"),
        ("b4_gqa_bf16", 4, 8, 4, 64, 16, 8, 0.75, "bf16"),
        ("b4_gqa_int8", 4, 8, 4, 64, 16, 8, 0.75, "int8"),
        ("b4_gqa_int4", 4, 8, 4, 64, 16, 8, 0.75, "int4"),
    ]

    def _time(fn, operands, iters):
        out = fn(*operands)  # compile
        _sync(out)
        _sync(fn(*operands))
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            _sync(fn(*operands))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    def _gather_decode(q, kr, vr, lens, sk=None, sv=None, fmt="f32"):
        # The engine's gather-path math verbatim: gathered [max_len]
        # view (dequantized first when quantized), grouped einsum with
        # the positional mask, f32 softmax.
        batch, heads, head_dim = q.shape
        kv_heads = kr.shape[2]
        group = heads // kv_heads
        if fmt == "int8":
            kr = dequantize_kv(kr, sk, q.dtype)
            vr = dequantize_kv(vr, sv, q.dtype)
        elif fmt == "int4":
            kr = dequantize_kv4(kr, sk, q.dtype)
            vr = dequantize_kv4(vr, sv, q.dtype)
        qg = q.reshape(batch, kv_heads, group, 1, head_dim)
        s = jnp.einsum(
            "bhgqd,bkhd->bhgqk", qg, kr, preferred_element_type=jnp.float32
        ) * (head_dim ** -0.5)
        mask = jnp.arange(kr.shape[1])[None, None, None, None, :] < (
            lens[:, None, None, None, None]
        )
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(vr.dtype)
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p, vr)
        return out.reshape(batch, heads, head_dim)

    generation = tuning.device_generation()
    rows: dict[str, dict] = {}
    for name, batch, heads, kv_heads, head_dim, ps, pages, fill, fmt in shapes:
        dt = jnp.float32 if fmt == "f32" else jnp.bfloat16
        import zlib

        rng = jax.random.PRNGKey(zlib.crc32(name.encode()) % (1 << 31))
        ks = jax.random.split(rng, 4)
        n_pool = batch * pages + 1
        q = jax.random.normal(ks[0], (batch, heads, head_dim), dt)
        pool_k = jax.random.normal(ks[1], (n_pool, ps, kv_heads, head_dim), dt)
        pool_v = jax.random.normal(ks[2], (n_pool, ps, kv_heads, head_dim), dt)
        table = (
            jnp.arange(batch * pages, dtype=jnp.int32).reshape(batch, pages)
            + 1
        )
        max_len = pages * ps
        lens = jnp.asarray(
            [max(1, int(max_len * fill) - 3 * i) for i in range(batch)],
            jnp.int32,
        )
        sk = sv = None
        if fmt == "int8":
            pool_k, sk = quantize_kv(pool_k)
            pool_v, sv = quantize_kv(pool_v)
        elif fmt == "int4":
            pool_k, sk = quantize_kv4(pool_k)
            pool_v, sv = quantize_kv4(pool_v)
        splits = tuning.pick_num_splits(pages, generation)
        quant_kw = {"scale_k": sk, "scale_v": sv} if sk is not None else {}
        kernel_fn = jax.jit(
            lambda q, k, v, t, ln, **kw: paged_attention(q, k, v, t, ln, **kw)
        )
        operands = (q, pool_k, pool_v, table, lens)
        kernel_ms = _time(
            lambda *o: kernel_fn(*o, **quant_kw), operands, iters=7
        )

        def gather_full(q, k, v, t, ln):
            kr = k[t].reshape(batch, max_len, kv_heads, -1)
            vr = v[t].reshape(batch, max_len, kv_heads, -1)
            skr = sk[t].reshape(batch, max_len, kv_heads) if sk is not None else None
            svr = sv[t].reshape(batch, max_len, kv_heads) if sv is not None else None
            return _gather_decode(q, kr, vr, ln, skr, svr, fmt)

        gather_ms = _time(jax.jit(gather_full), operands, iters=7)
        # The old path is SLOW on CPU (the whole point of the row);
        # two timed iterations bound the phase's wall clock.
        single_fn = jax.jit(
            lambda q, k, v, t, ln: paged_attention(
                q, k, v, t, ln, num_splits=1, use_pallas=True, **quant_kw
            )
        )
        try:
            single_ms = _time(single_fn, operands, iters=2)
        except Exception as e:  # pragma: no cover - env without Pallas
            log(f"  kernels: single-pass lane unavailable ({e!r})")
            single_ms = None
        rows[name] = {
            "fmt": fmt,
            "batch": batch,
            "heads": heads,
            "kv_heads": kv_heads,
            "head_dim": head_dim,
            "page_size": ps,
            "pages": pages,
            "splits": splits,
            "kernel_ms": round(kernel_ms, 4),
            "gather_ms": round(gather_ms, 4),
            "single_ms": round(single_ms, 4) if single_ms else None,
            "kernel_vs_gather": round(gather_ms / kernel_ms, 3),
            "single_vs_gather": (
                round(gather_ms / single_ms, 3) if single_ms else None
            ),
        }
        log(
            "  kernels %-16s %-5s S=%d kernel %.3fms gather %.3fms "
            "single %sms -> %.2fx gather"
            % (
                name, fmt, splits, kernel_ms, gather_ms,
                f"{single_ms:.3f}" if single_ms else "-",
                gather_ms / kernel_ms,
            )
        )
    min_ratio = min(r["kernel_vs_gather"] for r in rows.values())
    int8_vs_bf16 = None
    if "b4_gqa_int8" in rows and "b4_gqa_bf16" in rows:
        int8_vs_bf16 = round(
            rows["b4_gqa_bf16"]["kernel_ms"] / rows["b4_gqa_int8"]["kernel_ms"],
            3,
        )
    block = {
        "generation": generation,
        "shapes": rows,
        "min_kernel_vs_gather": min_ratio,
        "int8_vs_bf16": int8_vs_bf16,
    }
    log(
        "perf-ledger row: | KERNELS split-K paged attention (%d shapes) | "
        "kernel vs gather min %.2fx (int8 vs bf16 %sx; splits from "
        "%s row) | - | `benchmark.py --model serving --kernel` | update "
        "on bench round |"
        % (len(rows), min_ratio, int8_vs_bf16, generation)
    )
    return block


def _run_overload_phase(eng, args, baseline_tps: float) -> dict:
    """OVERLOAD perf phase: a 2x sustained overload storm with mixed
    priorities through the SAME compiled engine, with the overload
    controller installed the way the serving CLI default installs it.

    What the row claims and how it is measured:

    - **hi-pri TTFT p99** — per-request submit→first-token wall time of
      the high-priority class, measured unloaded (requests run alone)
      then during the storm.  Priority admission is supposed to keep
      the two within 1.2x: high-priority work jumps the queue while
      normal/low absorb the wait.
    - **goodput ratio** — in-deadline completed tokens over all emitted
      tokens (the controller's own ledger): the fraction of chip work
      clients could actually use.
    - **sheds** — deadline-doomed low-priority requests must shed
      (expired) instead of occupying slots; ``pool_exact`` pins that
      sheds returned every page (free pool back to allocatable).

    The storm sizes itself from the measured decode throughput: total
    demanded tokens ≈ 2x what the engine can serve inside the low-pri
    deadline, so low-priority deadline-carrying requests genuinely
    cannot all fit — the shed path runs for real, not by injection."""
    from .engine_overload import OverloadConfig, OverloadController

    eng.overload = OverloadController(
        eng.max_slots,
        # Submit-side load shedding is disabled (huge wait factor) so
        # the phase's shed ledger isolates the DEADLINE path — the
        # storm's shape (which low-pri requests expire) stays a
        # function of measured drain, not of the drain-rate estimate
        # the previous phases happened to leave behind.
        OverloadConfig(target_queue_wait_s=0.25, shed_wait_factor=1e9),
        metrics=eng.metrics,
        flight=eng.flight,
    )
    n_new = args.decode_tokens
    prompt = lambda i: [  # noqa: E731 — same shape as the main jobs
        (13 * i + j) % eng.cfg.vocab_size for j in range(args.prompt_len)
    ]
    # Warm the admission-burst batch shapes a mixed-priority storm can
    # hit (2-wide and 3-wide groups pad to 2/4; 1 and slots-wide are
    # already warm from the main serving warmup).
    eng.run([(prompt(90 + i), 2) for i in range(2)])
    eng.run([(prompt(94 + i), 2) for i in range(3)])

    def _ttft_p99(reqs):
        ttfts = sorted(
            r.first_token_at - r.submitted_at
            for r in reqs
            if r.first_token_at
        )
        if not ttfts:
            return None
        return ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]

    # Unloaded baseline: high-priority requests with the engine to
    # themselves.
    unloaded = []
    for i in range(4):
        unloaded += eng.run([(prompt(i), n_new)], priority=0)
    hi_unloaded = _ttft_p99(unloaded)

    # The storm: slots high + 2*slots normal + 2*slots low, all at
    # once — a queue several times deeper than the engine.  Low-pri
    # requests carry a deadline sized to HALF the storm's expected
    # drain time: since priority admission serves them last, the tail
    # genuinely cannot finish in time and must shed.
    n_hi = eng.max_slots
    n_norm = 2 * eng.max_slots
    n_low = 2 * eng.max_slots
    est_drain_s = ((n_hi + n_norm + n_low) * n_new) / max(baseline_tps, 1.0)
    low_deadline_s = max(est_drain_s / 2, 0.05)
    goodput0 = eng.overload.goodput_tokens
    raw0 = eng.overload.raw_tokens
    sheds0 = eng.overload.sheds_total
    storm: list = []
    hi_reqs = []
    for i in range(n_norm):
        storm.append(
            eng.submit(prompt(10 + i), n_new, priority=1, tenant="norm")
        )
    for i in range(n_low):
        storm.append(
            eng.submit(
                prompt(30 + i), n_new, priority=2, tenant="low",
                deadline_s=low_deadline_s,
            )
        )
    for i in range(n_hi):
        req = eng.submit(prompt(50 + i), n_new, priority=0, tenant="hi")
        storm.append(req)
        hi_reqs.append(req)
    t0 = time.perf_counter()
    guard = 0
    while not all(r.done for r in storm):
        eng.step()
        guard += 1
        if guard > 200_000:
            raise RuntimeError("overload storm failed to drain")
    storm_s = time.perf_counter() - t0
    hi_storm = _ttft_p99(hi_reqs)
    sheds = eng.overload.sheds_total - sheds0
    goodput = eng.overload.goodput_tokens - goodput0
    raw = eng.overload.raw_tokens - raw0
    pool_exact = (
        len(eng.free_pages) == eng.paged.num_pages - 1
        and all(s is None for s in eng.slots)
    )
    ratio = (hi_storm / hi_unloaded) if hi_unloaded and hi_storm else None
    block = {
        "storm_requests": len(storm),
        "storm_seconds": round(storm_s, 2),
        "low_deadline_s": round(low_deadline_s, 3),
        "hi_ttft_p99_unloaded_ms": (
            round(hi_unloaded * 1e3, 3) if hi_unloaded else None
        ),
        "hi_ttft_p99_storm_ms": (
            round(hi_storm * 1e3, 3) if hi_storm else None
        ),
        "hi_ttft_p99_ratio": round(ratio, 3) if ratio else None,
        "goodput_tokens": goodput,
        "raw_tokens": raw,
        "goodput_ratio": round(goodput / raw, 3) if raw else None,
        "sheds": sheds,
        "sheds_by_kind": dict(eng.overload.shed_counts),
        "limit_final": round(eng.overload.limit, 2),
        "pool_exact": pool_exact,
    }
    log(
        "perf-ledger row: | OVERLOAD control (b%d, %d-req storm) | "
        "hi-pri TTFT p99 %s -> %s ms (%sx), goodput %s, %d sheds, pool "
        "exact %s | - | `benchmark.py --model serving` | update on bench "
        "round |"
        % (
            eng.max_slots,
            len(storm),
            block["hi_ttft_p99_unloaded_ms"],
            block["hi_ttft_p99_storm_ms"],
            block["hi_ttft_p99_ratio"],
            block["goodput_ratio"],
            sheds,
            pool_exact,
        )
    )
    eng.overload = None  # leave the engine the way the next phase expects
    return block


def _run_slo_phase(eng, args) -> dict:
    """SLO perf phase: what the SLI/usage accounting seam costs on the
    SAME compiled engine (utils/slo.py; ISSUE 16).

    The same jobs decode with the SLO plane detached, then attached (a
    host-side toggle like the trace phase — no new compiles); the
    per-token cost difference is the measured accounting overhead.
    tools/bench_diff.py screams SLO-OVERHEAD past 1%.  The block also
    self-checks the alert pipeline: a synthetic burn injected into the
    SAME tracker must fire the fast-burn page rule (bench_diff screams
    BURN-ALERT-MISSED if it ever doesn't)."""
    from ..utils.slo import SLOTracker, UsageMeter

    prompt = lambda i: [  # noqa: E731 — same shape as the main jobs
        (13 * i + j) % eng.cfg.vocab_size for j in range(args.prompt_len)
    ]
    jobs = [
        (prompt(120 + i), args.decode_tokens)
        for i in range(2 * eng.max_slots)
    ]
    eng.slo = None
    eng.usage = None
    t0 = time.perf_counter()
    off_done = eng.run(jobs)
    off_dt = time.perf_counter() - t0
    off_tokens = sum(len(r.tokens) for r in off_done)
    eng.slo = SLOTracker()
    eng.usage = UsageMeter()
    t0 = time.perf_counter()
    on_done = eng.run(jobs)
    on_dt = time.perf_counter() - t0
    on_tokens = sum(len(r.tokens) for r in on_done)
    off_tps = off_tokens / off_dt if off_dt else 0.0
    on_tps = on_tokens / on_dt if on_dt else 0.0
    overhead = (off_tps / on_tps) - 1.0 if on_tps else 0.0
    verdicts = sum(pair[1] for pair in eng.slo.totals().values())
    tenants_metered = eng.usage.snapshot()["tracked_tenants"]
    # Alert-pipeline self-check on the live tracker: a synthetic
    # sustained burn (50% bad availability, budget 0.001) must fire the
    # fast-burn page rule on the next evaluation.
    eng.slo.record("availability", True, n=50)
    eng.slo.record("availability", False, n=50)
    burn_alert_fired = any(
        t["state"] == "fired" and t["rule"] == "fast_burn"
        for t in eng.slo.evaluate()
    )
    eng.slo = None  # leave the engine the way the next phase expects
    eng.usage = None
    block = {
        "overhead": round(overhead, 4),
        "off_tokens_per_sec": round(off_tps, 2),
        "on_tokens_per_sec": round(on_tps, 2),
        "sli_verdicts": verdicts,
        "tenants_metered": tenants_metered,
        "burn_alert_fired": burn_alert_fired,
    }
    log(
        "perf-ledger row: | SLO accounting (b%d) | slo off %.2f → on "
        "%.2f tokens/sec (overhead %+.2f%%; %d verdicts, burn alert "
        "fired %s) | - | `benchmark.py --model serving` | update on "
        "bench round |"
        % (
            eng.max_slots,
            off_tps,
            on_tps,
            overhead * 100.0,
            verdicts,
            burn_alert_fired,
        )
    )
    return block


def _run_restart_phase(eng, args) -> dict:
    """RESTART perf phase: cold vs warm post-restart TTFT through the
    crash-safe KV-arena snapshot (models/engine_snapshot.py).

    What the row claims and how it is measured:

    - A session set sharing a full-page prompt prefix runs once to warm
      the tiers, then the arena persists to disk (the fence/drain/
      SIGTERM save).  The "restart" is modeled on the SAME compiled
      engine — ``kvcache_clear()`` is exactly the serving state a
      process death loses, while the XLA programs stand in for the
      restarted pod's persistent compilation cache
      (JAX_COMPILATION_CACHE_DIR); the genuinely-fresh-process path is
      scored by the warm-restart chaos scenario.
    - **cold** restart: tiers cleared, no snapshot — every session
      re-prefills its prefix; per-request TTFT from the request's own
      submit/first-token stamps (requests run serially so TTFT is
      prefill, not queue wait).
    - **warm** restart: tiers cleared, snapshot REHYDRATED — prefix
      pages restore host->device instead of recomputing; same sessions,
      same stamps.  The restore scatter shape is compiled during the
      warmup pass so neither measured pass eats a compile.
    """
    import tempfile

    from .engine_snapshot import load_arena_snapshot, save_arena_snapshot

    page = eng.paged.page_size
    plen = args.prompt_len
    pl = (plen // page) * page  # the shareable FULL-page prefix
    if pl < page:
        return {"skipped": f"prompt_len {plen} < one page ({page})"}
    prefix = [(17 + j) % eng.cfg.vocab_size for j in range(pl)]
    sessions = [
        prefix + [(70 + 3 * s + j) % eng.cfg.vocab_size
                  for j in range(plen - pl)]
        for s in range(4)
    ]
    n_new = args.decode_tokens

    def _ttfts(reqs):
        return sorted(
            r.first_token_at - r.submitted_at
            for r in reqs
            if r.first_token_at
        )

    def _q(sorted_vals, q):
        if not sorted_vals:
            return None
        return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]

    # Warmup: populate the tiers, force the offload path, and compile
    # the restore scatter (one restore round) before anything is timed.
    eng.kvcache_clear()
    for s in sessions:
        eng.run([(s, n_new)])
    with eng._lock:
        eng._kv_reclaim(len(eng._kv_retained))
    eng.run([(sessions[0], n_new)])  # restore-path compile
    snapdir = tempfile.mkdtemp(prefix="tpu-kv-restart-")
    path = f"{snapdir}/kv_arena.snapshot"
    saved = save_arena_snapshot(eng, path, trigger="bench")
    if not saved.get("ok"):
        return {"skipped": f"snapshot save failed: {saved.get('reason')}"}

    # COLD restart: serving state gone, nothing rehydrated.
    eng.kvcache_clear()
    hits0 = eng.kv_host_hits
    cold_reqs = [eng.run([(s, n_new)])[0] for s in sessions]
    cold_hits = eng.kv_host_hits - hits0
    cold = _ttfts(cold_reqs)

    # WARM restart: same death, snapshot rehydrated first.
    eng.kvcache_clear()
    loaded = load_arena_snapshot(eng, path)
    hits0, restores0 = eng.kv_host_hits, eng.kv_restores
    warm_reqs = [eng.run([(s, n_new)])[0] for s in sessions]
    warm_hits = eng.kv_host_hits - hits0
    restored_pages = eng.kv_restores - restores0
    warm = _ttfts(warm_reqs)
    eng.kvcache_clear()

    cold_p99, warm_p99 = _q(cold, 0.99), _q(warm, 0.99)
    block = {
        "sessions": len(sessions),
        "prefix_tokens": pl,
        "snapshot_bytes": saved["bytes"],
        "snapshot_entries": saved["entries"],
        "entries_loaded": loaded.get("restored", 0),
        "cold": {
            "ttft_p50_ms": round(_q(cold, 0.5) * 1e3, 3),
            "ttft_p99_ms": round(cold_p99 * 1e3, 3),
            "prefix_hits": cold_hits,
        },
        "warm": {
            "ttft_p50_ms": round(_q(warm, 0.5) * 1e3, 3),
            "ttft_p99_ms": round(warm_p99 * 1e3, 3),
            "prefix_hits": warm_hits,
            "restored_pages": restored_pages,
        },
        "warm_speedup": round(cold_p99 / warm_p99, 3) if warm_p99 else None,
    }
    log(
        "perf-ledger row: | RESTART warm vs cold (b%d, %d sessions) | "
        "post-restart TTFT p99 cold %.3f → warm %.3f ms (%.3fx; %d pages "
        "restored, %d arena entries, snapshot %d B) | - | `benchmark.py "
        "--model serving` | update on bench round |"
        % (
            eng.max_slots,
            len(sessions),
            block["cold"]["ttft_p99_ms"],
            block["warm"]["ttft_p99_ms"],
            block["warm_speedup"] or 0.0,
            restored_pages,
            loaded.get("restored", 0),
            saved["bytes"],
        )
    )
    return block


def _run_elastic_phase(eng, args) -> dict:
    """ELASTIC perf phase: cold-join vs peer-warmed-join TTFT p99 over
    shared-prefix sessions (ISSUE 14 — elastic fleet scale-up).

    What the row claims and how it is measured:

    - The "donor" is the SAME compiled engine after serving a
      shared-prefix session set: its warm state is serialized through
      ``engine_snapshot.encode_snapshot`` — byte-for-byte the stream a
      real donor's ``GET /debug/snapshot`` sends a joining replica.
    - A **cold join** is modeled by clearing every KV tier (exactly
      what a fresh replica lacks) and serving the same sessions: every
      prefix re-prefills.  Per-request TTFT from the request's own
      submit/first-token stamps, requests serial so TTFT is prefill.
    - A **peer-warmed join** clears the same tiers, then rehydrates the
      donor's wire bytes through the same parse+verify+admit path
      ``fetch_peer_snapshot`` uses (minus the socket; the socket path
      itself is pinned in tier-1 and scored under chaos) — prefix
      pages restore host→device instead of recomputing.  The restore
      scatter compiles during the warmup pass so neither measured join
      eats a compile.

    The acceptance bar the diurnal-burst sim scores (warmed joiner's
    first-minute TTFT p99 within ~1.2x of warm peers) shows up here as
    ``warmed_speedup`` — a value below 1 means peer warm-up made the
    join SLOWER than cold and the ledger row screams NO-WARMUP.
    """
    import io

    from . import engine_snapshot as snap_mod

    page = eng.paged.page_size
    plen = args.prompt_len
    pl = (plen // page) * page  # the shareable FULL-page prefix
    if pl < page:
        return {"skipped": f"prompt_len {plen} < one page ({page})"}
    prefix = [(23 + j) % eng.cfg.vocab_size for j in range(pl)]
    sessions = [
        prefix + [(90 + 5 * s + j) % eng.cfg.vocab_size
                  for j in range(plen - pl)]
        for s in range(4)
    ]
    n_new = args.decode_tokens

    def _ttfts(reqs):
        return sorted(
            r.first_token_at - r.submitted_at
            for r in reqs
            if r.first_token_at
        )

    def _q(sorted_vals, q):
        if not sorted_vals:
            return None
        return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]

    # Donor warmup: serve the sessions, spill the retained tier into
    # the host arena (pool pressure's path), and compile the restore
    # scatter before anything is timed.
    eng.kvcache_clear()
    for s in sessions:
        eng.run([(s, n_new)])
    with eng._lock:
        eng._kv_reclaim(len(eng._kv_retained))
    eng.run([(sessions[0], n_new)])  # restore-path compile

    # The donor's wire stream: exactly what GET /debug/snapshot sends.
    with eng._lock:
        layout = snap_mod.snapshot_layout(eng)
        fingerprint = snap_mod.params_fingerprint(eng.params)
        entries = snap_mod.collect_entries(eng)
    wire = b"".join(snap_mod.encode_snapshot(layout, fingerprint, entries))

    # COLD join (the control): a fresh replica with no donor.
    eng.kvcache_clear()
    hits0 = eng.kv_host_hits
    cold_reqs = [eng.run([(s, n_new)])[0] for s in sessions]
    cold_hits = eng.kv_host_hits - hits0
    cold = _ttfts(cold_reqs)

    # PEER-WARMED join: same fresh replica, donor stream rehydrated
    # through the fetch path's parse+verify+admit before first traffic.
    eng.kvcache_clear()
    _, parsed = snap_mod._parse_snapshot(
        io.BytesIO(wire), layout, fingerprint
    )
    restored_entries = snap_mod._admit_entries(eng, parsed)
    hits0, restores0 = eng.kv_host_hits, eng.kv_restores
    warm_reqs = [eng.run([(s, n_new)])[0] for s in sessions]
    warm_hits = eng.kv_host_hits - hits0
    restored_pages = eng.kv_restores - restores0
    warm = _ttfts(warm_reqs)
    eng.kvcache_clear()

    cold_p99, warm_p99 = _q(cold, 0.99), _q(warm, 0.99)
    block = {
        "sessions": len(sessions),
        "prefix_tokens": pl,
        "wire_bytes": len(wire),
        "entries": len(entries),
        "entries_restored": restored_entries,
        "cold_join": {
            "ttft_p50_ms": round(_q(cold, 0.5) * 1e3, 3),
            "ttft_p99_ms": round(cold_p99 * 1e3, 3),
            "prefix_hits": cold_hits,
        },
        "warmed_join": {
            "ttft_p50_ms": round(_q(warm, 0.5) * 1e3, 3),
            "ttft_p99_ms": round(warm_p99 * 1e3, 3),
            "prefix_hits": warm_hits,
            "restored_pages": restored_pages,
        },
        "warmed_speedup": (
            round(cold_p99 / warm_p99, 3) if warm_p99 else None
        ),
    }
    log(
        "perf-ledger row: | ELASTIC cold vs peer-warmed join (b%d, %d "
        "sessions) | join TTFT p99 cold %.3f → warmed %.3f ms (%.3fx; "
        "%d entries / %d pages restored over %d wire bytes) | - | "
        "`benchmark.py --model serving` | update on bench round |"
        % (
            eng.max_slots,
            len(sessions),
            block["cold_join"]["ttft_p99_ms"],
            block["warmed_join"]["ttft_p99_ms"],
            block["warmed_speedup"] or 0.0,
            restored_entries,
            restored_pages,
            len(wire),
        )
    )
    return block


def _run_disagg_phase(eng, args) -> dict:
    """DISAGG perf phase: decode ITL p99 flat-vs-growing as long-prompt
    prefill load scales (ISSUE 15 — disaggregated prefill/decode).

    What the row claims and how it is measured:

    - **Unloaded baseline**: chatty decode requests alone on the main
      (unified) bench engine; ITL p99 read from the same engine
      histogram operators scrape.
    - **Unified control**: the same chatty traffic while a long-prompt
      request is injected every K steps — the injected prefill chunks
      run on the SAME step loop, so chatty ITL inflates (the problem
      disaggregation removes).
    - **Disagg**: a fresh decode-ROLE engine serves the chatty traffic;
      the long prompts' prefill runs on the unified engine standing in
      as the prefill pool, their finished pages cross through the REAL
      wire encoding (encode_preamble/encode_entry → the snapshot
      verifier → the arena), and the decode engine admits each long
      request by restoring pages and skipping the covered chunks.  The
      injection rate is DOUBLED vs the control — the acceptance bar is
      decode ITL p99 within ~1.2x of unloaded while prefill load
      doubles, with the unified control regressing.
    - **Oracle**: one injected long request's tokens on the decode
      engine must be bit-identical to the unified engine's (greedy —
      the handoff acceptance pin, at serving scale).
    """
    import io

    from . import engine_handoff as handoff_mod
    from . import engine_snapshot as snap_mod
    from .engine import EngineMetrics, ServingEngine

    from ..utils.metrics import MetricsRegistry

    page = eng.paged.page_size
    long_new = 4
    # Long prompts fill the paged window minus their tiny decode budget
    # — the longest prefill this engine can be asked for.
    long_len = ((eng.paged.max_len - long_new - 2) // page) * page
    if long_len < 2 * page or long_len <= args.prompt_len:
        return {
            "skipped": f"max_len {eng.paged.max_len} leaves no room for a "
            "long prompt"
        }
    chatty_prompts = [
        [(13 * i + j) % eng.cfg.vocab_size for j in range(args.prompt_len)]
        for i in range(max(2, args.slots - 1))
    ]
    long_prompts = [
        [(17 * i + 29 + j) % eng.cfg.vocab_size for j in range(long_len)]
        for i in range(8)
    ]
    interval = 24  # steps between injected long prompts (control rate)
    chatty_new = max(args.decode_tokens, 6 * interval // len(chatty_prompts))

    def _measure(engine, inject=None):
        """(itl_p99_s, injected request handles) for one traffic run.

        ITL is measured as per-STEP wall time: every active chatty slot
        emits exactly one token per step, so the step wall clock IS
        that token's inter-token gap — same quantity the
        tpu_engine_itl_seconds histogram aggregates, without its bucket
        quantization (a 1.2x acceptance bar needs exact quantiles)."""
        gaps: list[float] = []
        reqs = [engine.submit(p, chatty_new) for p in chatty_prompts]
        injected = []
        steps = 0
        while any(not r.done for r in reqs):
            t0 = time.perf_counter()
            engine.step()
            gaps.append(time.perf_counter() - t0)
            steps += 1
            if inject is not None:
                got = inject(steps)
                if got is not None:
                    injected.append(got)
        # Drain injected stragglers outside the measured window's
        # bookkeeping (their decode rides the same loop either way).
        guard = 0
        while any(not r.done for r in injected):
            engine.step()
            guard += 1
            if guard > 50_000:
                raise RuntimeError("disagg phase failed to drain")
        ordered = sorted(gaps)
        p99 = ordered[min(int(0.99 * len(ordered)), len(ordered) - 1)]
        return p99, injected

    # The unified engine stands in for BOTH the control and the prefill
    # pool; chunked prefill on both sides so the comparison is the
    # architecture, not the chunking.
    prev_chunk = eng._prefill_chunk
    eng._prefill_chunk = page * 2

    def _warm_mixed(engine, pre_admit=None):
        """Untimed warmup replicating the measured traffic SHAPE: the
        long admission lands in the same slot, with the same occupied
        chatty slots, as it will during measurement — so slot-indexed
        scatters and the long-bucket chunk programs compile here, not
        inside a measured p99."""
        reqs = [engine.submit(p, 8) for p in chatty_prompts]
        long_req = None
        steps = 0
        while any(not r.done for r in reqs) or (
            long_req is not None and not long_req.done
        ):
            engine.step()
            steps += 1
            if steps == 2:
                if pre_admit is not None:
                    pre_admit()
                long_req = engine.submit(long_prompts[0], long_new)
        engine.kvcache_clear()

    eng.kvcache_clear()
    try:
        # Warmup (untimed): the long-bucket chunk program + one full
        # mixed-slot round.
        _warm_mixed(eng)

        # --- Unloaded baseline ------------------------------------------
        itl_unloaded, _ = _measure(eng)

        # --- Unified control: long prefills share the decode loop -------
        def inject_unified(step, _next=[0]):
            if step % interval or _next[0] >= len(long_prompts) // 2:
                return None
            prompt = long_prompts[_next[0]]
            _next[0] += 1
            return eng.submit(prompt, long_new)

        itl_unified, _ = _measure(eng, inject_unified)

        # --- Disagg: decode-role engine + wire-transferred prefixes -----
        import dataclasses as _dc

        dec = ServingEngine(
            _dc.replace(eng.cfg, paged=None),
            eng.params,
            eng.paged,
            max_slots=eng.max_slots,
            metrics=EngineMetrics(MetricsRegistry()),
            prefill_chunk=page * 2,
            kv_retain=True,
            kv_host_cache_mb=64,
            role="decode",
        )
        # The prefill pool's output, as wire bytes (the donor ran the
        # long prefills above and retains their pages; entries re-read
        # through the resident path are the bytes /v1/prefill streams).
        eng.kvcache_clear()
        with eng._lock:
            layout = snap_mod.snapshot_layout(eng)
            fingerprint = snap_mod.params_fingerprint(eng.params)
        wires = []
        oracle_tokens = []
        for prompt in long_prompts:
            # The donor run doubles as the LOCAL-PREFILL ORACLE: greedy
            # tokens for the same prompt, same compiled programs.  The
            # wire then comes from a REAL prefill probe (the tap path
            # /v1/prefill serves), entries + shipped logits.
            oracle_tokens.append(list(eng.run([(prompt, long_new)])[0].tokens))
            tap = eng.handoff_begin(prompt, None)
            entries = []
            try:
                for _ in range(10_000):
                    eng.step()
                    while True:
                        e = tap.pop(0.0)
                        if e is None:
                            break
                        entries.append(e)
                    if tap.req.done and tap.pushed <= len(entries):
                        break
            finally:
                eng.handoff_end(tap)
            wires.append(
                snap_mod.encode_preamble(layout, fingerprint, len(entries))
                + b"".join(
                    snap_mod.encode_entry(layout, k, r) for k, r in entries
                )
                + (
                    handoff_mod.encode_logits_section(tap.logits)
                    if tap.logits is not None
                    else b""
                )
            )
            eng.kvcache_clear()

        def _admit_wire(idx):
            buf = io.BytesIO(wires[idx])
            _, parsed = snap_mod._parse_snapshot(buf, layout, fingerprint)
            admitted = snap_mod._admit_entries(dec, parsed)
            logits = handoff_mod.read_logits_section(buf)
            if logits is not None:
                with dec._lock:
                    dec._kv_arena.put(
                        ("logits", -1, tuple(long_prompts[idx])),
                        {"logits": logits},
                        logits.nbytes,
                    )
            return admitted
        # Warmup the decode engine: the same mixed shape, with the long
        # admission arriving as a HANDOFF (restore scatter + seeded
        # tail chunk + mixed-slot graft all compile here).
        dec.run([(chatty_prompts[0], 2)])

        _warm_mixed(dec, pre_admit=lambda: _admit_wire(0))
        assert dec.handoff_skipped_tokens > 0, (
            "disagg warmup never skipped covered prefill"
        )

        handoff_entries = 0

        def inject_disagg(step, _next=[0]):
            # DOUBLE the control's prefill load: every interval/2 steps.
            nonlocal handoff_entries
            if step % (interval // 2) or _next[0] >= len(long_prompts) // 2:
                return None
            idx = _next[0]
            _next[0] += 1
            handoff_entries += _admit_wire(idx)
            return dec.submit(long_prompts[idx], long_new)

        itl_disagg, disagg_long = _measure(dec, inject_disagg)
        tokens_match = bool(disagg_long) and [
            list(r.tokens) for r in disagg_long
        ] == oracle_tokens[: len(disagg_long)]
    finally:
        eng._prefill_chunk = prev_chunk
        eng.kvcache_clear()

    def _ms(value):
        return None if value is None else round(value * 1e3, 3)

    unified_ratio = (
        round(itl_unified / itl_unloaded, 3)
        if itl_unified and itl_unloaded
        else None
    )
    disagg_ratio = (
        round(itl_disagg / itl_unloaded, 3)
        if itl_disagg and itl_unloaded
        else None
    )
    block = {
        "prefill_jobs": len(long_prompts) // 2,
        "long_prompt_tokens": long_len,
        "itl_p99_unloaded_ms": _ms(itl_unloaded),
        "unified": {
            "itl_p99_loaded_ms": _ms(itl_unified),
            "ratio": unified_ratio,
        },
        "disagg": {
            "itl_p99_loaded_ms": _ms(itl_disagg),
            "ratio": disagg_ratio,
            "handoff_entries": handoff_entries,
            "skipped_prefill_tokens": dec.handoff_skipped_tokens,
            "tokens_match": tokens_match,
        },
    }
    log(
        "perf-ledger row: | DISAGG prefill/decode split (b%d, %d-token "
        "prefills) | decode ITL p99 %.3f ms unloaded → unified %.3f "
        "(%.2fx) vs disagg %.3f ms at 2x prefill load (%.2fx; %d entries "
        "shipped, %d prefill tokens skipped, tokens %s) | - | "
        "`benchmark.py --model serving` | update on bench round |"
        % (
            eng.max_slots,
            long_len,
            block["itl_p99_unloaded_ms"] or 0.0,
            block["unified"]["itl_p99_loaded_ms"] or 0.0,
            unified_ratio or 0.0,
            block["disagg"]["itl_p99_loaded_ms"] or 0.0,
            disagg_ratio or 0.0,
            handoff_entries,
            dec.handoff_skipped_tokens,
            "bit-identical" if tokens_match else "DIVERGED",
        )
    )
    return block


def run_serving(args) -> None:
    """Continuous-batching serving benchmark through the SAME telemetry
    operators scrape: the TTFT/ITL percentiles in the JSON line are read
    back from the EngineMetrics histograms on the registry (PromQL-style
    bucket interpolation, utils/metrics.py Histogram.quantile), not from
    a parallel stopwatch path — so BENCH rounds and Grafana dashboards
    report the same numbers, and a drift between them is itself a bug.

    The decode loop is timed TWICE over the same job set — synchronous
    (overlap off) then overlapped (the serving default) — and the JSON
    line carries both, so every bench round records what keeping one
    step in flight buys on this link (plus the hit/discard counts that
    say whether the pipeline actually stayed primed)."""
    import math

    from ..utils.metrics import MetricsRegistry
    from ..utils.spans import SpanRecorder
    from .engine import EngineMetrics, ServingEngine
    from .transformer import PagedConfig, TransformerLM

    import dataclasses

    page_size = 16
    mpp = math.ceil((args.prompt_len + args.decode_tokens) / page_size)
    paged = PagedConfig(
        page_size,
        num_pages=args.slots * mpp + 1,
        max_pages_per_seq=mpp,
    )
    cfg = dataclasses.replace(_gpt_config(args), max_seq=paged.max_len)
    rng = jax.random.PRNGKey(0)
    params = TransformerLM(cfg).init(
        rng, jnp.zeros((1, 2), jnp.int32)
    )["params"]
    registry = MetricsRegistry()
    spans = SpanRecorder()
    eng = ServingEngine(
        cfg,
        params,
        paged,
        max_slots=args.slots,
        metrics=EngineMetrics(registry),
        spans=spans,
        kv_retain=True,
        kv_host_cache_mb=64,
    )
    jobs = [
        (
            [(11 * i + j) % cfg.vocab_size for j in range(args.prompt_len)],
            args.decode_tokens,
        )
        for i in range(args.requests)
    ]
    # Warmup compiles prefill + step outside the timed region (the repo's
    # measurement-honesty rule); the histogram snapshots below subtract
    # its compile-dominated observations from the reported quantiles.
    # Both pipeline modes run the SAME compiled step program (the overlap
    # knob selects host-side scheduling, not a new program), so one
    # warmup covers the pair — but it must cover BOTH admission-burst
    # prefill shapes the timed runs hit (slots-wide initial burst and
    # the single-request mid-drain refill), or whichever mode runs first
    # would eat the missing compile inside its timed region.
    eng.run([(jobs[0][0], 2)])
    eng.run([(p, 2) for p, _ in jobs[: args.slots]])

    # Synchronous baseline FIRST (any residual warm-cache bias then works
    # against the overlapped number, not for it): same jobs, overlap off.
    eng._overlap_steps = 0
    t0 = time.perf_counter()
    sync_done = eng.run(jobs)
    sync_dt = time.perf_counter() - t0
    sync_tokens = sum(len(r.tokens) for r in sync_done)
    sync_tps = sync_tokens / sync_dt

    ttft_h, itl_h = eng.metrics.ttft_seconds, eng.metrics.itl_seconds
    ttft_snap, itl_snap = ttft_h.snapshot(), itl_h.snapshot()

    def _ms(value):
        return None if value is None else round(value * 1e3, 3)

    # The headline run: overlapped pipeline (the serving default).
    eng._overlap_steps = 1
    hits0, discards0 = eng.overlap_hits, eng.overlap_discards
    t0 = time.perf_counter()
    done = eng.run(jobs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in done)
    overlap_tps = tokens / dt
    log(
        "perf-ledger row: | Overlapped decode pipeline (b%d) | sync %.2f "
        "→ overlapped %.2f tokens/sec (%.3fx; hits %d, discards %d) | - "
        "| `benchmark.py --model serving` | update on bench round |"
        % (
            args.slots,
            round(sync_tps, 2),
            round(overlap_tps, 2),
            overlap_tps / sync_tps if sync_tps else 0.0,
            eng.overlap_hits - hits0,
            eng.overlap_discards - discards0,
        )
    )
    # The SAME per-step profile /debug/profile serves on a live server
    # (models/engine_profiler.py): per-phase p50/p99 over the rolling
    # window — so a BENCH round records where the steps' time went, not
    # just how many tokens came out.
    prof = eng.profiler.snapshot()
    phase_p50 = {
        phase: stats["window_p50_ms"]
        for phase, stats in prof["phases"].items()
        if stats["window_steps"]
    }
    log(
        "perf-ledger row: | Serving step phase breakdown (b%d) | step p50 "
        "%.3f ms (%s) | - | `benchmark.py --model serving` ≡ GET "
        "/debug/profile | update on bench round |"
        % (
            args.slots,
            prof["step_ms"]["p50"],
            ", ".join(f"{k} {v:.3f}" for k, v in phase_p50.items()),
        )
    )

    # --- KV cache tiering: repeated-prefix + preemption-churn workload ---
    # Phase 1: one hot prompt with SERIAL (non-overlapping) lifetimes, so
    # live prefix sharing cannot help — only the retained tier can.  Timed
    # with tiering off (every lifetime re-grafts its prompt pages) then on
    # (pages revive off the retained LRU; the graft skips them).
    prefix_job = (jobs[0][0], args.decode_tokens)
    n_rep = min(args.requests, 6)
    eng._kv_retain = False
    eng.kvcache_clear()
    t0 = time.perf_counter()
    rec_tokens = sum(
        len(r.tokens) for _ in range(n_rep) for r in eng.run([prefix_job])
    )
    dt_recompute = time.perf_counter() - t0
    eng._kv_retain = True
    eng.kvcache_clear()
    kv_hits0 = eng.kv_retained_hits + eng.kv_host_hits
    t0 = time.perf_counter()
    res_tokens = sum(
        len(r.tokens) for _ in range(n_rep) for r in eng.run([prefix_job])
    )
    dt_restore = time.perf_counter() - t0
    kv_hits = eng.kv_retained_hits + eng.kv_host_hits - kv_hits0
    rec_tps = rec_tokens / dt_recompute if dt_recompute else 0.0
    res_tps = res_tokens / dt_restore if dt_restore else 0.0
    kv_speedup = res_tps / rec_tps if rec_tps else 0.0

    # Phase 2: preemption churn — optimistic admission over a deliberately
    # tightened pool (free pages parked aside), so growing slots preempt
    # their juniors and the victims resume.  With the tiers on, resumes
    # restore (zero prefill re-run) instead of recomputing.
    eng.kvcache_clear()
    pre0 = eng.preemptions
    resumes0 = eng.kv_resumes_restored
    recomputes0 = eng.kv_resumes_recompute
    eng._optimistic = True
    page_size = eng.paged.page_size
    prompt_pages = (args.prompt_len + 1 + page_size - 1) // page_size
    keep = mpp + 2 * prompt_pages  # oldest can finish; juniors must churn
    with eng._lock:
        parked = [
            eng.free_pages.pop()
            for _ in range(max(0, len(eng.free_pages) - keep))
        ]
    churn_done = eng.run(jobs[: max(2, args.slots)])
    churn_tokens = sum(len(r.tokens) for r in churn_done)
    with eng._lock:
        eng.kvcache_clear()
        for page in parked:
            eng.free_pages.append(page)
    eng._optimistic = False
    churn_preempts = eng.preemptions - pre0
    churn_restored = eng.kv_resumes_restored - resumes0
    churn_recomputed = eng.kv_resumes_recompute - recomputes0
    log(
        "perf-ledger row: | KV cache tiering (b%d) | repeated-prefix "
        "recompute %.2f → restore %.2f tokens/sec (%.3fx; tier hits %d) "
        "| preemption churn: %d preempts, %d restored / %d recomputed "
        "resumes | `benchmark.py --model serving` | update on bench round |"
        % (
            args.slots,
            rec_tps,
            res_tps,
            kv_speedup,
            kv_hits,
            churn_preempts,
            churn_restored,
            churn_recomputed,
        )
    )

    # --- Tracing overhead phase (TRACE row) ------------------------------
    # The always-on span layer must stay ~free: the SAME jobs decode
    # through the SAME compiled programs with the recorder detached,
    # then attached (host-side toggle — no new compiles), and the
    # per-token cost difference is the measured tracing overhead.
    # tools/bench_diff.py screams TRACE-OVERHEAD past 2%.
    trace_spans0 = len(spans.snapshot()) + spans.dropped
    eng.spans = None
    t0 = time.perf_counter()
    off_done = eng.run(jobs)
    trace_off_dt = time.perf_counter() - t0
    off_tokens = sum(len(r.tokens) for r in off_done)
    eng.spans = spans
    t0 = time.perf_counter()
    on_done = eng.run(jobs)
    trace_on_dt = time.perf_counter() - t0
    on_tokens = sum(len(r.tokens) for r in on_done)
    trace_off_tps = off_tokens / trace_off_dt if trace_off_dt else 0.0
    trace_on_tps = on_tokens / trace_on_dt if trace_on_dt else 0.0
    trace_overhead = (
        (trace_off_tps / trace_on_tps) - 1.0 if trace_on_tps else 0.0
    )
    trace_spans_recorded = (
        len(spans.snapshot()) + spans.dropped - trace_spans0
    )
    trace_block = {
        "overhead": round(trace_overhead, 4),
        "off_tokens_per_sec": round(trace_off_tps, 2),
        "on_tokens_per_sec": round(trace_on_tps, 2),
        "spans_recorded": trace_spans_recorded,
    }
    log(
        "perf-ledger row: | Tracing overhead (b%d) | spans off %.2f → on "
        "%.2f tokens/sec (overhead %+.2f%%; %d spans) | - | `benchmark.py "
        "--model serving` | update on bench round |"
        % (
            args.slots,
            trace_off_tps,
            trace_on_tps,
            trace_overhead * 100.0,
            trace_spans_recorded,
        )
    )

    # --- Tensor-parallel phase (MULTICHIP row) ---------------------------
    # Same jobs through a tp=N engine built the CLI-facing way
    # (mesh_from_allocation + the sharded ctor), timed against the tp=1
    # overlapped number above.  Gated on a multi-device backend whose
    # head counts the tp degree divides; the row carries decode tokens/s
    # at tp=1 vs tp=N, the scaling efficiency, discards under tp, and
    # whether the token streams stayed bit-identical.
    tp_block = None
    tp_n = len(jax.devices())
    if tp_n > 1 and cfg.kv_heads % tp_n == 0 and cfg.num_heads % tp_n == 0:
        from ..parallel.mesh import mesh_from_allocation

        tp_mesh = mesh_from_allocation(tp_n)
        tp_eng = ServingEngine(
            cfg,
            params,
            paged,
            max_slots=args.slots,
            metrics=EngineMetrics(MetricsRegistry()),
            mesh=tp_mesh,
            kv_retain=True,
            kv_host_cache_mb=64,
        )
        # Warmup MUST cover the tp-sharded step/block shapes: sharded
        # params and pools compile DISTINCT executables, so reusing the
        # single-chip warmup above would charge the tp compiles to the
        # first measured round (the r6 warmup bug).  Same two shapes the
        # tp=1 warmup covers — single prefill and the slots-wide burst.
        tp_eng.run([(jobs[0][0], 2)])
        tp_eng.run([(p, 2) for p, _ in jobs[: args.slots]])
        tp_discards0 = tp_eng.overlap_discards
        t0 = time.perf_counter()
        tp_done = tp_eng.run(jobs)
        tp_dt = time.perf_counter() - t0
        tp_tokens = sum(len(r.tokens) for r in tp_done)
        tp_tps = tp_tokens / tp_dt if tp_dt else 0.0
        tp_match = [r.tokens for r in tp_done] == [r.tokens for r in done]
        tp_speedup = tp_tps / overlap_tps if overlap_tps else 0.0
        tp_block = {
            "size": tp_n,
            "tokens_per_sec": round(tp_tps, 2),
            "tp1_tokens_per_sec": round(overlap_tps, 2),
            "speedup": round(tp_speedup, 3),
            "scaling_efficiency": round(tp_speedup / tp_n, 3),
            "discards": tp_eng.overlap_discards - tp_discards0,
            "tokens_match": tp_match,
        }
        log(
            "perf-ledger row: | MULTICHIP tensor-parallel serving "
            "(tp=%d, b%d) | tp=1 %.2f → tp=%d %.2f tokens/sec (%.3fx, "
            "efficiency %.3f; discards %d; tokens %s) | - | `benchmark.py "
            "--model serving` | update on bench round |"
            % (
                tp_n,
                args.slots,
                overlap_tps,
                tp_n,
                tp_tps,
                tp_speedup,
                tp_speedup / tp_n,
                tp_block["discards"],
                "bit-identical" if tp_match else "DIVERGED",
            )
        )
    # --- Kernels phase (KERNELS rows): split-K vs gather vs single-pass
    kernels_block = _run_kernels_phase(args)
    # --- Overload phase (OVERLOAD row): 2x storm, mixed priorities -----
    overload_block = _run_overload_phase(eng, args, overlap_tps)
    # --- Restart phase (RESTART row): cold vs warm arena rehydration ---
    restart_block = _run_restart_phase(eng, args)
    # --- Elastic phase (ELASTIC row): cold vs peer-warmed join ---------
    elastic_block = _run_elastic_phase(eng, args)
    # --- Disagg phase (DISAGG row): decode ITL under prefill load ------
    disagg_block = _run_disagg_phase(eng, args)
    # --- Router phase (ROUTER row): affinity vs random placement -------
    router_block = _run_router_phase(args)
    # --- Fabric phase (FABRIC row): fleet KV vs affinity-only control --
    fabric_block = _run_fabric_phase(args)
    # --- SLO phase (SLO row): accounting overhead + alert self-check ---
    slo_block = _run_slo_phase(eng, args)
    # --- Canary phase (CANARY row): prober overhead + detection check --
    canary_block = _run_canary_phase(args)
    # --- Autoscale phase (AUTOSCALE row): controller vs static peak ----
    autoscale_block = _run_autoscale_phase(args)
    # --- Postmortem phase (POSTMORTEM row): capture overhead + verdict -
    postmortem_block = _run_postmortem_phase(args)
    print(
        json.dumps(
            {
                "model": "serving",
                "chips": len(jax.devices()),
                "slots": args.slots,
                "requests": len(done),
                "prompt_len": args.prompt_len,
                "new_tokens": args.decode_tokens,
                "throughput": round(tokens / dt, 2),
                "unit": "tokens/sec (continuous batching, warm, "
                "overlapped pipeline)",
                "overlap": {
                    "tokens_per_sec": round(overlap_tps, 2),
                    "sync_tokens_per_sec": round(sync_tps, 2),
                    "speedup": round(overlap_tps / sync_tps, 3)
                    if sync_tps
                    else None,
                    "hits": eng.overlap_hits - hits0,
                    "discards": eng.overlap_discards - discards0,
                },
                "ttft_p50_ms": _ms(ttft_h.quantile(0.5, since=ttft_snap)),
                "ttft_p99_ms": _ms(ttft_h.quantile(0.99, since=ttft_snap)),
                "itl_p50_ms": _ms(itl_h.quantile(0.5, since=itl_snap)),
                "itl_p99_ms": _ms(itl_h.quantile(0.99, since=itl_snap)),
                "kvcache": {
                    "prefix_recompute_tokens_per_sec": round(rec_tps, 2),
                    "prefix_restore_tokens_per_sec": round(res_tps, 2),
                    "restore_speedup": round(kv_speedup, 3),
                    "hits": kv_hits,
                    "retained_hits": eng.kv_retained_hits,
                    "host_hits": eng.kv_host_hits,
                    "restores": eng.kv_restores,
                    "reclaims": eng.kv_reclaims,
                    "offloads": eng.kv_offloads,
                    "churn_tokens": churn_tokens,
                    "preemptions": churn_preempts,
                    "resumes_restored": churn_restored,
                    "resumes_recomputed": churn_recomputed,
                },
                "tp": tp_block,
                "kernels": kernels_block,
                "overload": overload_block,
                "restart": restart_block,
                "elastic": elastic_block,
                "disagg": disagg_block,
                "router": router_block,
                "fabric": fabric_block,
                "slo": slo_block,
                "canary": canary_block,
                "autoscale": autoscale_block,
                "postmortem": postmortem_block,
                "trace": trace_block,
                "spans_recorded": len(spans.snapshot()) + spans.dropped,
                "profile": {
                    "steps": prof["steps"],
                    "step_ms_p50": prof["step_ms"]["p50"],
                    "step_ms_p99": prof["step_ms"]["p99"],
                    "phase_ms_p50": phase_p50,
                    "occupancy": prof["occupancy"],
                    "trace_overhead": trace_block["overhead"],
                    "incidents": eng.anomaly.snapshot()["incidents_total"],
                },
            }
        ),
        flush=True,
    )


def run_pipelined(args) -> None:
    """Decoder-LM training through the pipelined path (--pp stages) —
    the in-pod way to exercise pp on a multi-chip allocation, with either
    schedule.  Reports tokens/sec like the gpt path."""
    from ..parallel.mesh import make_mesh
    from ..parallel.pipeline_lm import PipelinedLM

    if args.model != "gpt":
        raise SystemExit("--pp requires --model gpt (the pipelined decoder)")
    cfg = _gpt_config(args)
    devices = jax.devices()
    if len(devices) < args.pp:
        raise SystemExit(f"--pp {args.pp} but only {len(devices)} device(s)")
    if cfg.num_layers % args.pp:
        raise SystemExit(
            f"num_layers {cfg.num_layers} not divisible by --pp {args.pp}"
        )
    mesh = make_mesh({"pp": args.pp}, devices=devices[: args.pp])
    plm = PipelinedLM(cfg, mesh, n_micro=args.n_micro)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(
        rng, (args.batch_size, args.seq_len + 1), 0, cfg.vocab_size
    )
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    tx = optax.sgd(0.1, momentum=0.9)
    micro_rows = max(args.batch_size // args.n_micro, 1)
    state = plm.create_train_state(
        plm.init(rng, batch["input_ids"][:micro_rows]), tx
    )
    step = jax.jit(
        plm.make_train_step(tx, schedule=args.pp_schedule), donate_argnums=0
    )
    state, loss, dt = timed_steps(step, state, batch, args.warmup, args.steps)
    tokens = args.batch_size * args.seq_len * args.steps
    print(
        json.dumps(
            {
                "model": "gpt-pp",
                "schedule": args.pp_schedule,
                **device_facts(),
                "chips": len(devices),
                "pp": args.pp,
                "n_micro": args.n_micro,
                "global_batch": args.batch_size,
                "throughput": round(tokens / dt, 2),
                "unit": "tokens/sec",
                "step_time_ms": round(dt / args.steps * 1e3, 2),
                "final_loss": float(loss),
            }
        ),
        flush=True,
    )


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(prog="tpu-benchmark")
    p.add_argument(
        "--model",
        choices=[
            "alexnet", "resnet50", "vit", "bert", "gpt", "gpt-decode",
            "serving",
        ],
        default="resnet50",
    )
    p.add_argument("--batch-size", type=int, default=128, help="GLOBAL batch size")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--seq-len", type=int, default=384)
    p.add_argument("--steps", type=_positive_int, default=30)
    p.add_argument("--warmup", type=_positive_int, default=5)
    p.add_argument("--dp", type=int, default=-1, help="data-parallel axis size (-1: all devices)")
    p.add_argument("--mp", type=int, default=1, help="param-sharding axis size")
    p.add_argument(
        "--pp",
        type=int,
        default=0,
        help="pipeline stages (gpt only): run the decoder through the "
        "pipelined-LM path over a pp mesh axis instead of dp/mp",
    )
    p.add_argument(
        "--pp-schedule",
        choices=["gpipe", "1f1b"],
        default="gpipe",
        help="pipeline schedule (with --pp): gpipe (autodiff backward) or "
        "1f1b (interleaved, O(stages) activation memory)",
    )
    p.add_argument(
        "--n-micro",
        type=_positive_int,
        default=4,
        help="microbatches per step in the pipelined path (with --pp)",
    )
    p.add_argument(
        "--fused-xent",
        action="store_true",
        help="gpt only: fused LM-head + cross-entropy loss tail "
        "(ops/fused_xent.py) — the [batch, seq, vocab] logits tensor "
        "never materializes",
    )
    p.add_argument("--prompt-len", type=_positive_int, default=64, help="gpt-decode/serving prompt")
    p.add_argument("--decode-tokens", type=_positive_int, default=128, help="gpt-decode/serving new tokens")
    p.add_argument(
        "--slots",
        type=_positive_int,
        default=4,
        help="serving: engine decode slots (continuous-batching width)",
    )
    p.add_argument(
        "--requests",
        type=_positive_int,
        default=16,
        help="serving: synthetic requests pushed through the engine",
    )
    p.add_argument(
        "--kernel",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="serving: run the KERNELS phase (split-K paged-attention "
        "kernel vs the gather fallback vs the old single-pass lane, per "
        "shape x KV format — the per-shape ledger tools/bench_diff.py "
        "gates; --no-kernel skips it)",
    )
    p.add_argument(
        "--router-replicas",
        type=int,
        default=2,
        help="serving: replicas in the ROUTER phase (prefix-affinity vs "
        "random-placement control over K tiny real serving replicas "
        "behind the router daemon; 0/1 skips the phase)",
    )
    p.add_argument(
        "--temperature",
        type=float,
        default=None,
        help="gpt-decode: sample with this temperature instead of greedy argmax",
    )
    p.add_argument(
        "--top-k", type=_positive_int, default=None,
        help="gpt-decode: restrict sampling to the k highest logits",
    )
    p.add_argument(
        "--stem",
        choices=["conv7", "space_to_depth"],
        default="conv7",
        help="resnet50 stem: standard 7x7/s2 conv or the space-to-depth "
        "packing (geometry-equivalent, MXU-friendlier — models/resnet.py)",
    )
    p.add_argument(
        "--grad-accum",
        type=_positive_int,
        default=1,
        help="microbatches per optimizer step (one scanned program; "
        "activation memory of one microbatch, full-batch update math) — "
        "the GLOBAL batch must divide evenly",
    )
    p.add_argument("--tiny", action="store_true", help="tiny model config (CPU smoke; gpt and vit)")
    p.add_argument(
        "--trace-dir",
        default=tracing.default_trace_dir(),
        help="write a jax.profiler trace of the timed region here",
    )
    p.add_argument(
        "--checkpoint-dir",
        default="",
        help="orbax checkpoint directory (models/checkpoint.py). When set, "
        "the run saves every --checkpoint-every steps and at exit, so a "
        "preempted pod (health fault, node drain — the BASELINE config-5 "
        "scenario) can resume instead of restarting. ≙ SURVEY §5.4: the "
        "reference plugin is stateless because the kubelet checkpoints "
        "device assignments; the WORKLOAD side must checkpoint itself.",
    )
    p.add_argument(
        "--checkpoint-every",
        type=_positive_int,
        default=10,
        help="steps between async checkpoint saves (with --checkpoint-dir)",
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="restore the newest checkpoint under --checkpoint-dir before "
        "training; --steps is then the ABSOLUTE target step, so a resumed "
        "run finishes the remaining steps",
    )
    args = p.parse_args(argv)

    # A restarted benchmark pod (node drain, preemption — the --resume
    # scenario) reuses its compilations.
    enable_compilation_cache(log=log)

    # Multi-host (k8s-job-resnet50-2host.yaml): stitch processes over DCN,
    # derived from the plugin-injected TPU_WORKER_* env (or explicit JAX_*
    # overrides — parallel/distributed.py).  jax.devices() then spans the
    # slice and the dp axis crosses hosts.
    if distributed.initialize():
        log(f"jax.distributed: process {jax.process_index()}/{jax.process_count()}")

    # Validate flag combinations BEFORE any model construction so a wrong
    # pod spec fails in milliseconds with a clear message, and no path can
    # silently ignore a requested behavior.
    if args.fused_xent and args.model != "gpt":
        raise SystemExit("--fused-xent requires --model gpt")
    if args.grad_accum > 1 and (
        args.fused_xent or args.pp > 1 or args.model == "gpt-decode"
    ):
        raise SystemExit(
            "--grad-accum applies to the standard train step only (the "
            "fused-xent and pipelined steps manage their own "
            "microbatching, and gpt-decode does not train)"
        )
    if args.grad_accum > 1 and args.batch_size % args.grad_accum:
        raise SystemExit(
            f"--batch-size {args.batch_size} is not divisible by "
            f"--grad-accum {args.grad_accum}"
        )
    if args.fused_xent and args.pp > 1:
        raise SystemExit(
            "--fused-xent is not supported with --pp (the pipelined LM head "
            "runs inside the 1F1B/GPipe objective); drop one of the flags"
        )

    if args.model == "gpt-decode":
        run_decode(args)
        return

    if args.model == "serving":
        run_serving(args)
        return

    if args.pp > 1:
        run_pipelined(args)
        return

    devices = jax.devices()
    log(f"devices: {[str(d) for d in devices]}")
    mesh = make_slice_mesh({"dp": args.dp, "mp": args.mp})
    log(f"mesh: {dict(mesh.shape)}")

    rng = jax.random.PRNGKey(0)
    model, batch, input_key, items_per_step = build(args.model, args, rng)
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_train_state(rng, model, batch, tx, input_key=input_key)
    if args.fused_xent:
        from .train import make_fused_lm_train_step

        step_fn = make_fused_lm_train_step(model, tx)
        log("loss tail: fused LM-head + cross-entropy (no logits tensor)")
    else:
        step_fn = make_train_step(
            model, tx, input_key=input_key, grad_accum=args.grad_accum
        )
        if args.grad_accum > 1:
            log(f"grad accumulation: {args.grad_accum} microbatches/step")
    step, state, batch_sh = shard_train_step(step_fn, mesh, state, batch)
    if jax.process_count() > 1:
        # Each process owns a slice of the global batch; assemble global
        # arrays from process-local shards (the SPMD multi-host idiom).
        n = jax.process_count()

        def globalize(x, sh):
            per = x.shape[0] // n
            pid = jax.process_index()
            local = np.asarray(x)[pid * per : (pid + 1) * per]
            return jax.make_array_from_process_local_data(sh, local)

        batch = jax.tree.map(globalize, batch, batch_sh)
    else:
        batch = jax.device_put(batch, batch_sh)

    resumed_from = 0
    if args.checkpoint_dir:
        from .checkpoint import CheckpointManager

        ckpt = CheckpointManager(args.checkpoint_dir)
        if args.resume and ckpt.latest_step() is not None:
            # Restore AFTER shard_train_step placed the state: orbax lands
            # every leaf directly in its NamedSharding, no host round-trip.
            state = ckpt.restore(state)
            resumed_from = int(jax.device_get(state.step))
            log(f"resumed from checkpoint step {resumed_from}")
        if resumed_from >= args.steps:
            log(
                f"WARNING: checkpoint already at step {resumed_from} >= "
                f"--steps {args.steps}; nothing to train. Stale checkpoint "
                f"dir from a previous run? Clear it (or raise --steps) to "
                f"re-benchmark."
            )
        with tracing.trace(args.trace_dir):
            state, loss, dt, steps_run = checkpointed_steps(
                step,
                state,
                batch,
                args.steps,
                ckpt,
                args.checkpoint_every,
                warmup=args.warmup,
            )
        ckpt.close()
    else:
        with tracing.trace(args.trace_dir):
            state, loss, dt = timed_steps(step, state, batch, args.warmup, args.steps)
        steps_run = args.steps

    n_chips = len(devices)
    throughput = items_per_step * steps_run / dt if dt > 0 else 0.0
    unit = "tokens/sec" if args.model in ("bert", "gpt") else "images/sec"
    record = {
        "model": args.model,
        **device_facts(),
        "chips": n_chips,
        "global_batch": args.batch_size,
        "throughput": round(throughput, 2),
        "throughput_per_chip": round(throughput / n_chips, 2),
        "unit": unit,
        "step_time_ms": round(dt / steps_run * 1e3, 2) if steps_run else 0.0,
        "final_loss": float(loss) if loss is not None else None,
        # Two-point timing executes warmup + (warmup+steps) steps total, so
        # final_step exceeds --steps; it is the truth about how far the
        # state advanced (checkpoint runs advance exactly to --steps).
        "final_step": int(jax.device_get(state.step)),
    }
    if args.checkpoint_dir:
        record["resumed_from"] = resumed_from
        # Stale-checkpoint rerun guard: True when this invocation trained
        # nothing at all (checkpoint was already at/over --steps).
        record["noop"] = record["final_step"] == resumed_from
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
