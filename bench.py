"""Headline benchmark: ResNet-50 training images/sec on one TPU chip.

Matches BASELINE.json's metric ("AlexNet/ResNet-50 images/sec/chip in k8s
pod") and the measurement style of the reference's benchmark pod (synthetic
data, steady-state timing — reference k8s-pod-example-gpu.yaml runs the
convnet-benchmarks AlexNet timing script).

One process, one chip.  It refuses to run when ``jax.devices()[0]`` is not
a TPU (exit 2, no result line): a number from the CPU is never written
under this metric's name.  On the chip it prints the headline JSON line on
stdout —

    {"metric": ..., "value": N, "unit": "images/sec/chip", "mfu": N,
     "platform": "tpu", "device_kind": "...", "device_count": N}

— then runs the secondary benches (per-model numbers, flash-attention
speedup, allocation latency; stderr).  A secondary that raises is logged
with its traceback, the others still run, and the exit code is 1.

The cell benchmark of ROADMAP Speed 1 replaces this script.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))


# --------------------------------------------------------------------------
# The benchmark (jax imported only here, so importing this module is cheap)
# --------------------------------------------------------------------------


def main() -> int:
    import jax

    from k8s_device_plugin_tpu.utils.platform import (
        device_facts,
        enable_compilation_cache,
        peak_bf16_flops,
    )

    facts = device_facts()
    if facts["platform"] != "tpu":
        print(
            f"bench.py measures the chip and found platform "
            f"{facts['platform']!r} ({facts['device_kind']}, "
            f"{facts['device_count']} device(s)); refusing to run",
            file=sys.stderr,
            flush=True,
        )
        return 2
    # Caching affects compile time only: every timed region starts after
    # its programs are compiled.
    enable_compilation_cache()

    import jax.numpy as jnp
    import optax

    from k8s_device_plugin_tpu.models.benchmark import (
        _sync,
        chained_tps,
        log,
        measure_two_point,
        timed_steps,
    )
    from k8s_device_plugin_tpu.models.data import synthetic_image_batch
    from k8s_device_plugin_tpu.models.resnet import ResNet50
    from k8s_device_plugin_tpu.models.train import create_train_state, make_train_step

    log(
        f"platform: {facts['platform']} ({facts['device_kind']}, "
        f"{facts['device_count']} device(s))"
    )
    # Raises for a device_kind the peaks table does not know.
    peak = peak_bf16_flops(jax.devices()[0])

    # ResNet-50 at 224x224: 4.1 GMACs = 8.2 GFLOP forward per image (2
    # FLOPs per multiply-accumulate — the same true-FLOP convention the
    # LM bench's 6ND count uses); training (fwd + bwd) ~= 3x forward.
    # Used only for MFU reporting — throughput stays the headline metric.
    RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.2e9

    # steps=60 was chosen in the builder session of 2026-08-01 (before
    # PR 1, record deleted in PR 21) to dilute a per-dispatch constant
    # that machine had; not re-measured.
    def bench_resnet50(batch_size: int, steps: int = 60, warmup: int = 5) -> float:
        rng = jax.random.PRNGKey(0)
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        batch = synthetic_image_batch(rng, batch_size, image_size=224, num_classes=1000)
        tx = optax.sgd(0.1, momentum=0.9)
        state = create_train_state(rng, model, batch, tx)
        step = jax.jit(make_train_step(model, tx), donate_argnums=0)

        state, loss, dt = timed_steps(step, state, batch, warmup, steps)
        ips = batch_size * steps / dt
        log(f"resnet50 b{batch_size}: {steps} steps in {dt:.2f}s -> {ips:.1f} images/sec")
        return ips

    def bench_resnet_variants() -> None:
        """Secondary: ResNet levers A/B'd against the headline
        configuration on the same chip (stderr only).  bf16 BatchNorm
        output is the headline default (builder session 2026-08-01,
        before PR 1, not re-measured: 2630 vs 2071 images/sec); the
        f32-BN variant keeps the regression visible, and the
        space-to-depth stem stays on watch."""
        rng = jax.random.PRNGKey(0)
        batch = synthetic_image_batch(rng, 128, image_size=224, num_classes=1000)
        tx = optax.sgd(0.1, momentum=0.9)
        for label, bsz, kw in [
            ("f32-BN", 128, dict(norm_dtype=jnp.float32)),
            ("s2d-stem", 128, dict(stem="space_to_depth")),
            ("b256", 256, dict()),
        ]:
            vbatch = (
                batch
                if bsz == 128
                else synthetic_image_batch(
                    rng, bsz, image_size=224, num_classes=1000
                )
            )
            model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, **kw)
            state = create_train_state(rng, model, vbatch, tx)
            step = jax.jit(make_train_step(model, tx), donate_argnums=0)
            # Same chain length as the headline, so the A/B is like for like.
            state, loss, dt = timed_steps(step, state, vbatch, 5, 60)
            log(f"resnet50 variant {label}: {bsz * 60 / dt:.1f} images/sec")

    def bench_lm_train() -> None:
        """Secondary: decoder-LM training tokens/sec on one chip (stderr only)."""
        from k8s_device_plugin_tpu.models.transformer import GPTConfig, TransformerLM

        cfg = GPTConfig(
            vocab_size=32000,
            hidden_size=1024,
            num_layers=8,
            num_heads=16,
            intermediate_size=2816,
            max_seq=1024,
        )
        batch_size, seq, steps, warmup = 8, 1024, 20, 5
        model = TransformerLM(cfg)
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (batch_size, seq + 1), 0, cfg.vocab_size)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        tx = optax.adamw(1e-3)
        state = create_train_state(rng, model, batch, tx, input_key="input_ids")
        step = make_train_step(model, tx, input_key="input_ids")
        state, loss, dt = timed_steps(step, state, batch, warmup, steps)
        tps = batch_size * seq * steps / dt
        log(f"transformer-lm b{batch_size} s{seq}: {tps:.0f} tokens/sec (loss {float(loss):.3f})")
        # 6 FLOPs per matmul param per token (fwd+bwd) plus the
        # causal-halved attention matmuls (6*L*seq*hidden); embedding
        # gathers excluded.
        from jax.tree_util import tree_flatten_with_path

        n_matmul = sum(
            leaf.size
            for path, leaf in tree_flatten_with_path(state.params)[0]
            if getattr(leaf, "ndim", 0) >= 2
            and "emb" not in str(path).lower()
        )
        fpt = 6 * n_matmul + 6 * cfg.num_layers * seq * cfg.hidden_size
        log(
            f"transformer-lm MFU: {tps * fpt / peak:.1%} "
            f"({n_matmul/1e6:.0f}M matmul params)"
        )
        # Fused LM-head + xent tail (ops/fused_xent.py): same model,
        # no [b,s,vocab] logits tensor — report the delta, then the
        # chunk-size sweep.
        from k8s_device_plugin_tpu.models.train import make_fused_lm_train_step

        state2 = create_train_state(rng, model, batch, tx, input_key="input_ids")
        fstep = make_fused_lm_train_step(model, tx)
        state2, floss, fdt = timed_steps(fstep, state2, batch, warmup, steps)
        ftps = batch_size * seq * steps / fdt
        log(
            f"transformer-lm fused-xent: {ftps:.0f} tokens/sec "
            f"({ftps / max(tps, 1e-9):.2f}x vs naive tail, loss {float(floss):.3f})"
        )
        for chunk in (cfg.vocab_size // 8, cfg.vocab_size // 2, cfg.vocab_size):
            s3 = create_train_state(rng, model, batch, tx, input_key="input_ids")
            cstep = make_fused_lm_train_step(model, tx, chunk=chunk)
            s3, _, cdt = timed_steps(cstep, s3, batch, warmup, steps)
            ctps = batch_size * seq * steps / cdt
            log(
                f"  fused-xent chunk {chunk}: {ctps:.0f} tokens/sec "
                f"({ctps / max(tps, 1e-9):.2f}x vs naive)"
            )

    def timed_chain(fn, x, iters: int, small: int = 2) -> float:
        """Seconds per application of ``fn`` (shape-preserving, x -> x).

        Chains applications inside ONE compiled `lax.fori_loop` (each
        iteration consumes the previous output, so nothing can be elided)
        and times two chain lengths; the difference covers exactly
        ``iters`` applications with dispatch/sync overhead cancelled.
        """

        def chain(n):
            @jax.jit
            def run(x):
                c = jax.lax.fori_loop(0, n, lambda i, c: fn(c), x)
                # Scalar result: the sync copies four bytes, not the tensor.
                return jnp.mean(c, dtype=jnp.float32)

            return run

        run_s, run_b = chain(small), chain(small + iters)
        jax.device_get(run_s(x))  # compile
        jax.device_get(run_b(x))
        dt, fell_back = measure_two_point(
            lambda: jax.device_get(run_s(x)),
            lambda: jax.device_get(run_b(x)),
            iters,
            small + iters,
        )
        if fell_back:
            log("  (chain delta below noise floor; single-point)")
        return dt / iters

    def bench_flash_attention() -> None:
        """Secondary: fused flash kernel speedup over plain-XLA attention."""
        from k8s_device_plugin_tpu.ops.flash_attention import (
            flash_attention,
            mha_reference,
        )

        shape = (4, 16, 2048, 64)
        iters = 20
        b, h, s, d = shape
        q = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
        # Distinct k/v buffers: q,q,q lets Mosaic/XLA alias all three
        # operands to one HBM buffer and dedupe tile fetches, flattering
        # the ms and TFLOP/s — no real model has q=k=v.
        kfa = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.bfloat16)
        vfa = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.bfloat16)
        t_flash = timed_chain(
            lambda q: flash_attention(q, kfa, vfa, causal=True), q, iters
        )
        t_ref = timed_chain(
            lambda q: mha_reference(q, kfa, vfa, causal=True), q, iters
        )
        # Causal attention FLOPs: 2 matmuls * b*h*s*s*d, halved by masking.
        flops = 2 * 2 * b * h * s * s * d / 2
        log(
            f"flash-attention {shape}: {t_flash*1e3:.2f} ms vs XLA "
            f"{t_ref*1e3:.2f} ms ({t_ref/t_flash:.2f}x, "
            f"{flops/t_flash/1e12:.1f} TFLOP/s)"
        )
        # Block sweep: one small config as a canary, the rest at the
        # large end where the per-generation defaults sit.
        for bq, bkv in [(128, 512), (256, 512), (512, 512), (512, 1024), (512, 2048), (1024, 1024)]:
            t = timed_chain(
                lambda q, bq=bq, bkv=bkv: flash_attention(
                    q, kfa, vfa, causal=True, block_q=bq, block_kv=bkv
                ),
                q,
                iters,
            )
            log(f"  block sweep q{bq}/kv{bkv}: {t*1e3:.2f} ms ({flops/t/1e12:.1f} TFLOP/s)")
        # GQA variant: 4x fewer kv heads must cut kv HBM traffic.
        hk = shape[1] // 4
        kg = jax.random.normal(jax.random.PRNGKey(4), (b, hk, s, d), jnp.bfloat16)
        vg = jax.random.normal(jax.random.PRNGKey(5), (b, hk, s, d), jnp.bfloat16)
        t = timed_chain(
            lambda q: flash_attention(q, kg, vg, causal=True), q, iters
        )
        log(f"  GQA {shape[1]}q/{hk}kv heads: {t*1e3:.2f} ms ({flops/t/1e12:.1f} TFLOP/s)")
        # Fused Pallas backward (dQ + dK/dV kernels) vs the chunked XLA
        # backward: each chain application is a full fwd+bwd (dq feeds
        # the next iteration — shape-preserving).
        for impl in ("pallas", "xla"):
            t = timed_chain(
                lambda q, impl=impl: jax.grad(
                    lambda qq: flash_attention(
                        qq, kfa, vfa, causal=True, bwd_impl=impl
                    ).astype(jnp.float32).sum()
                )(q),
                q,
                max(iters // 2, 2),
            )
            # fwd 2 matmuls + bwd 5 matmul-equivalents (incl. the
            # per-stage recompute), causal-halved.
            bwd_flops = 7 * b * h * s * s * d / 2 * 2
            log(
                f"  fwd+bwd ({impl}): {t*1e3:.2f} ms "
                f"({bwd_flops/t/1e12:.1f} TFLOP/s)"
            )

    def bench_paged_kernel() -> None:
        """Secondary: the Mosaic-compiled paged-attention kernel vs the
        gather path at serving shapes (stderr only)."""
        import numpy as np

        from k8s_device_plugin_tpu.ops.paged_attention import paged_attention

        configs = [
            ("b4 len512 ps16", 4, 16, 4, 64, 16, 64, 512),
            ("b8 len1024 ps16", 8, 16, 4, 64, 16, 128, 1024),
            ("b8 len2048 ps32", 8, 16, 4, 64, 32, 64, 2048),
        ]
        iters = 30
        for (label, b, h, kv, d, ps, mpp, fill) in configs:
            n_pool = b * mpp + 1
            ks = jax.random.split(jax.random.PRNGKey(0), 4)
            q0 = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
            pk = jax.random.normal(ks[1], (n_pool, ps, kv, d), jnp.bfloat16)
            pv = jax.random.normal(ks[2], (n_pool, ps, kv, d), jnp.bfloat16)
            # Scrambled non-contiguous pages — the serving layout.
            perm = jax.random.permutation(ks[3], n_pool - 1) + 1
            table = np.zeros((b, mpp), np.int32)
            need = -(-fill // ps)
            table[:, :need] = np.asarray(perm)[: b * need].reshape(b, need)
            table = jnp.asarray(table)
            lens = jnp.full((b,), fill, jnp.int32)

            def gather_ref(q):
                kr = pk[table].reshape(b, mpp * ps, kv, d)
                vr = pv[table].reshape(b, mpp * ps, kv, d)
                qg = q.reshape(b, kv, h // kv, 1, d)
                s = jnp.einsum(
                    "bhgqd,bkhd->bhgqk", qg, kr,
                    preferred_element_type=jnp.float32,
                ) * (d ** -0.5)
                mask = (
                    jnp.arange(mpp * ps)[None, None, None, None, :]
                    < lens[:, None, None, None, None]
                )
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(vr.dtype)
                return jnp.einsum("bhgqk,bkhd->bhgqd", p, vr).reshape(
                    b, h, d
                )

            t_k = timed_chain(
                lambda q: paged_attention(q, pk, pv, table, lens).astype(
                    q.dtype
                ),
                q0,
                iters,
            )
            t_g = timed_chain(
                lambda q: gather_ref(q).astype(q.dtype), q0, iters
            )
            log(
                f"paged-attention {label}: kernel {t_k*1e6:.0f} us vs "
                f"gather {t_g*1e6:.0f} us ({t_g/t_k:.2f}x)"
            )

    def bench_engine_serving() -> None:
        """Secondary: ServingEngine steady-state decode throughput at
        decode_block 1 vs 16 (stderr only).  Host-driven serving pays one
        dispatch per step; blocks amortize it.  Uses a small 4-layer GQA
        model to keep the compile short."""
        import numpy as np

        from k8s_device_plugin_tpu.models.engine import ServingEngine
        from k8s_device_plugin_tpu.models.transformer import (
            GPTConfig,
            PagedConfig,
            TransformerLM,
        )

        cfg = GPTConfig(
            vocab_size=32000,
            hidden_size=1024,
            num_layers=4,
            num_heads=16,
            intermediate_size=2816,
            max_seq=2048,
            num_kv_heads=4,
        )
        rng = jax.random.PRNGKey(0)
        params = TransformerLM(cfg).init(
            rng, jnp.zeros((1, 2), jnp.int32)
        )["params"]
        slots, prompt_len = 8, 256
        for block in (1, 16):
            # 48 pages x 16 = 768 slots per row >= 256 prompt + 400 new.
            paged = PagedConfig(
                page_size=16, num_pages=slots * 48 + 8, max_pages_per_seq=48
            )
            eng = ServingEngine(
                cfg, params, paged, max_slots=slots, decode_block=block
            )
            for i in range(slots):
                eng.submit(
                    list(
                        np.random.default_rng(i).integers(0, 32000, prompt_len)
                    ),
                    max_new_tokens=400,
                )
            for _ in range(3):  # admit + compile + settle
                eng.step()
            n_disp = max(4, 64 // block)
            before = sum(len(r.tokens) for r in eng.slots if r is not None)
            # A request that finishes inside the window vacates its slot,
            # so live-slot sums would drop its tokens from `after`; count
            # finished requests from step()'s return instead.
            fin_toks = 0
            t0 = time.perf_counter()
            for _ in range(n_disp):
                fin_toks += sum(len(r.tokens) for r in eng.step())
            dt = time.perf_counter() - t0
            after = sum(len(r.tokens) for r in eng.slots if r is not None)
            toks = after + fin_toks - before
            log(
                f"engine serving decode_block={block}: "
                f"{toks/dt:.0f} tokens/sec "
                f"({dt/n_disp*1e3:.1f} ms/dispatch, b{slots})"
            )

    def bench_allocation_latency() -> None:
        """Secondary metric from BASELINE.json: chip-allocation latency through
        the actual plugin gRPC path (fixture-backed, no cluster needed)."""
        import tempfile
        from concurrent import futures

        import grpc

        sys.path.insert(0, _REPO_ROOT)
        from tests.fakes import make_fake_tpu_host
        from k8s_device_plugin_tpu.kubelet.api import (
            DevicePluginStub,
            add_device_plugin_servicer,
            pb,
        )
        from k8s_device_plugin_tpu.plugin import discovery
        from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
        from k8s_device_plugin_tpu.plugin.server import TpuDevicePlugin

        root = make_fake_tpu_host(tempfile.mkdtemp(), n_chips=4)
        plugin = TpuDevicePlugin(
            discover=lambda: discovery.discover(root=root, environ={}),
            health_checker=ChipHealthChecker(root=root),
        )
        server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        add_device_plugin_servicer(plugin, server)
        sock = tempfile.mktemp(suffix=".sock")
        server.add_insecure_port(f"unix://{sock}")
        server.start()
        try:
            with grpc.insecure_channel(f"unix://{sock}") as ch:
                stub = DevicePluginStub(ch)
                req = pb.AllocateRequest(
                    container_requests=[
                        pb.ContainerAllocateRequest(devicesIDs=["tpu-0", "tpu-1"])
                    ]
                )
                stub.Allocate(req)  # warm
                t0 = time.perf_counter()
                n = 100
                for _ in range(n):
                    stub.Allocate(req)
                latency_ms = (time.perf_counter() - t0) / n * 1e3
        finally:
            server.stop(grace=None)
        log(f"plugin Allocate mean latency: {latency_ms:.2f} ms")

    def bench_decode_quant() -> None:
        """Secondary: int8-quantized decode throughput vs bf16 (stderr only).

        Decode is weight-bandwidth-bound at small batch, so w8 (int8
        weights dequantized in-register, ops/quant.py) should approach 2x
        the bf16 tokens/sec as batch shrinks.  2 layers: decode
        throughput per layer is what the quant modes change, and fewer
        layers halve the six decode-scan compiles.
        """
        import dataclasses

        from k8s_device_plugin_tpu.models.transformer import (
            GPTConfig,
            TransformerLM,
            greedy_generate,
        )
        from k8s_device_plugin_tpu.ops.quant import quantize_lm_params

        cfg = GPTConfig(
            vocab_size=32000,
            hidden_size=1024,
            num_layers=2,
            num_heads=16,
            intermediate_size=2816,
            max_seq=512,
            num_kv_heads=4,
        )
        batch, prompt_len, n_new = 8, 128, 128
        rng = jax.random.PRNGKey(0)
        params = TransformerLM(cfg).init(
            rng, jnp.zeros((1, 2), jnp.int32)
        )["params"]
        qparams = quantize_lm_params(params)
        prompt = jax.random.randint(rng, (batch, prompt_len), 0, cfg.vocab_size)

        def decode_tps(c, p):
            return batch * chained_tps(
                lambda n: _sync(greedy_generate(c, p, prompt, n)), 2, n_new
            )

        base = decode_tps(cfg, params)
        log(f"decode bf16: {base:.0f} tokens/sec (b{batch}, {cfg.num_layers}L)")
        w8 = decode_tps(dataclasses.replace(cfg, quant="w8"), qparams)
        log(f"decode w8 int8 weights: {w8:.0f} tokens/sec ({w8 / max(base, 1e-9):.2f}x bf16)")
        full = decode_tps(
            dataclasses.replace(cfg, quant="w8", quant_kv=True), qparams
        )
        log(
            f"decode w8 + int8 kv cache: {full:.0f} tokens/sec "
            f"({full / max(base, 1e-9):.2f}x bf16)"
        )

    def bench_speculative() -> None:
        """Secondary: int8 self-speculative decode (stderr only).

        The zero-extra-weights serving config — the draft is the SAME
        model w8-quantized; greedy verification makes the output exactly
        the bf16 greedy decode's.  Logs acceptance rate alongside
        tokens/sec: with synthetic (random-init) weights the draft/target
        agreement is the pessimistic floor, so read the ratio together
        with the acceptance number.
        """
        import dataclasses

        from k8s_device_plugin_tpu.models.speculative import (
            speculative_generate,
        )
        from k8s_device_plugin_tpu.models.transformer import (
            GPTConfig,
            TransformerLM,
            greedy_generate,
        )
        from k8s_device_plugin_tpu.ops.quant import quantize_lm_params

        cfg = GPTConfig(
            vocab_size=32000,
            hidden_size=1024,
            num_layers=2,
            num_heads=16,
            intermediate_size=2816,
            max_seq=512,
            num_kv_heads=4,
        )
        prompt_len, n_new, gamma = 128, 128, 4
        rng = jax.random.PRNGKey(0)
        params = TransformerLM(cfg).init(
            rng, jnp.zeros((1, 2), jnp.int32)
        )["params"]
        d_cfg = dataclasses.replace(cfg, quant="w8")
        d_params = quantize_lm_params(params)
        prompt = jax.random.randint(rng, (1, prompt_len), 0, cfg.vocab_size)

        base = chained_tps(
            lambda n: _sync(greedy_generate(cfg, params, prompt, n)),
            2,
            n_new,
            label="spec-base",
        )
        seq, acc = speculative_generate(
            cfg, params, d_cfg, d_params, prompt, n_new, gamma=gamma
        )
        rate = float(jnp.mean(acc.astype(jnp.float32)))
        spec = chained_tps(
            lambda n: _sync(
                speculative_generate(
                    cfg, params, d_cfg, d_params, prompt, n, gamma=gamma
                )[0]
            ),
            2,
            n_new,
            label="spec",
        )
        log(
            f"decode b1 bf16: {base:.0f} tokens/sec; w8 self-speculative "
            f"(gamma={gamma}): {spec:.0f} tokens/sec "
            f"({spec / max(base, 1e-9):.2f}x, acceptance {rate:.0%})"
        )

    ips = bench_resnet50(batch_size=128)
    mfu = ips * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak
    log(f"resnet50 MFU: {mfu:.1%} of {peak/1e12:.0f} TFLOP/s bf16 peak")
    # The headline prints BEFORE the secondary benches, so a caller that
    # kills a long run still has it.
    print(
        json.dumps(
            {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": round(ips, 2),
                "unit": "images/sec/chip",
                "mfu": round(mfu, 4),
                **facts,
            }
        ),
        flush=True,
    )
    failed: list[str] = []
    for secondary in (
        bench_decode_quant,
        bench_speculative,
        bench_paged_kernel,
        bench_engine_serving,
        bench_allocation_latency,
        bench_lm_train,
        bench_resnet_variants,
        bench_flash_attention,
    ):
        # The one boundary that keeps running: a secondary that raises is
        # reported with its traceback, the rest still run, and the exit
        # code says so.
        try:
            secondary()
        except Exception:
            failed.append(secondary.__name__)
            log(f"{secondary.__name__} FAILED:\n{traceback.format_exc()}")
    if failed:
        log(f"secondary benches failed: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
