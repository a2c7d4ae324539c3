#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout on a machine with a TPU:

    python chip_smoke.py

It drives the system's main paths once, through the entry points a user
would call, at the width the repo ships (weights random from PRNGKey(0),
prompts from a seed, no network, no git):

  probe    a child prints ``jax.devices()``; anything but TPU devices ends
           the run non-zero before any leg, naming what it found.
  plugin   the real daemon (``python -m k8s_device_plugin_tpu.plugin.cli
           --root /``) against the machine's own devfs/sysfs with
           tests/fakes.py:FakeKubelet as its peer: it must register, list
           as many healthy ``google.com/tpu`` devices as JAX reported
           chips, and ``Allocate`` them.  Every later chip child runs
           under exactly the environment that ``Allocate`` returned.
  serve    ``python -m k8s_device_plugin_tpu.models.http_server`` at the
           geometry of deploy/k8s-deploy-serve-http.yaml: a warm-up per
           prompt-length bucket, eight concurrent requests, one SSE
           stream, one prompt twice; /debug/state names the backend;
           SIGTERM exits 0.
  oracle   a CPU child (JAX_PLATFORMS=cpu — the oracle, never a stand-in
           for the chip) recomputes every generated token's
           log-probability with a plain float32 forward pass
           (``mha_reference``, no cache) of the same parameters.
  kernel   ``ops.paged_attention.paged_attention`` lowered by Mosaic at
           the shipped pool geometry for float, int8 and int4 pools
           against the gather path, then one server start with
           ``--use-kernel`` that answers a request.
  resnet50, gpt, gpt-decode
           ``python -m k8s_device_plugin_tpu.models.benchmark`` for a few
           steps: ResNet-50 b128 224x224 bf16 (the BASELINE.json metric),
           the dense LM at b8 s1024 (flash forward + Pallas backward),
           and a cached decode (flash in the bulk prefill).
  sharding (more than one chip only: the server then runs ``--tp N``,
           ResNet-50 data-parallel over all N, and ``gpt`` is left out —
           see planned_legs) the serving engine on a ``tp`` mesh over
           every chip: ``assert_sharded()``, bytes in use on each device,
           device ``coords`` beside the mesh order.

One process owns the chip at any moment: this parent never imports JAX
(nor anything that does), starts each leg as ONE child, and waits for it
to exit before the next.  Chip children get ``JAX_PLATFORMS=tpu``, so a
libtpu that cannot start is an error and not a CPU run.

Any failed check, any child with a non-zero exit, any leg that did not
run makes the exit code non-zero.  The last lines of stdout name the
platform, ``device_kind``, chip count and each leg with its result, wall
time, and XLA compile seconds apart from the rest; the very last line is
one JSON object, ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": N}, ...}``.

Compile cache: utils/platform.py's one rule — ``JAX_COMPILATION_CACHE_DIR``
if the machine sets it, else ``<checkout>/.jax_cache`` — so a second run
in the same checkout compiles from the cache (``cache_hits`` per leg).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "k8s_device_plugin_tpu"
# The contract allows 1200 s, compilation included; what is left is for
# stopping children and printing.
LIMIT_S = 1150.0

# deploy/k8s-deploy-serve-http.yaml: geometry and scheduler settings of
# the shipped replica (overload, SLO plane and watchdog at CLI defaults).
GEOMETRY = {
    "vocab": 32000,
    "hidden": 1024,
    "layers": 8,
    "heads": 16,
    "kv_heads": 4,
    "page_size": 16,
    "num_pages": 512,
    "max_pages_per_seq": 32,
}
SERVE_FLAGS = [
    "--hidden=1024", "--layers=8", "--heads=16", "--kv-heads=4",
    "--page-size=16", "--num-pages=512", "--max-pages-per-seq=32",
    "--slots=8", "--decode-block=16", "--admission=optimistic",
    "--prefill-chunk=256", "--kv-retain=1", "--kv-host-cache-mb=512",
    "--http-port=0",
]
SLOTS = 8
MAX_NEW = 64
# One prompt length per prefill bucket (128/256/512) to warm up, then
# eight concurrent prompts of 100-400 tokens.
WARMUP_LENGTHS = (100, 200, 400)
CONCURRENT_LENGTHS = (100, 140, 180, 220, 260, 300, 340, 400)
STREAM_LENGTH = 150
REPEAT_LENGTH = 200
SEED = 21

# Chip (bf16 activations and KV, f32 logits) against the float32 oracle.
# With PRNGKey(0) weights the logits are ~N(0, 1): the greedy token sits
# near -6.8 nats and a typical token near -10.9, so a wrong position, a
# wrong weight or a broken cache moves a log-probability by a nat or more.
# bf16 rounding through eight layers moves it by hundredths: the largest
# difference over all 13 prompts x 64 tokens measured on the v5e was
# 0.055 nats (chip run, PR 21).  0.25 is more than four times that and a
# quarter of the smallest error worth catching.
LOGPROB_TOL = 0.25
# Paged kernel against the gather path, bf16 outputs of magnitude ~1
# (bf16 eps 2^-8 = 0.0039): measured 0.0054 / 0.0108 / 0.0089 for float /
# int8 / int4 pools on the v5e, the same at 1 and 8 splits (chip run,
# PR 21); a masking or paging error is O(0.1-1).
KERNEL_TOL = 0.03

TRAIN_LEGS = {
    "resnet50": [
        "--model", "resnet50", "--batch-size", "128", "--image-size", "224",
        "--steps", "3", "--warmup", "1",
    ],
    "gpt": [
        "--model", "gpt", "--batch-size", "8", "--seq-len", "1024",
        "--steps", "3", "--warmup", "1",
    ],
    "gpt-decode": [
        "--model", "gpt-decode", "--batch-size", "8", "--prompt-len", "128",
        "--decode-tokens", "16",
    ],
}

_XLA_COMPILE_RE = re.compile(r"Finished XLA compilation of (.*) in ([0-9.]+) sec")
_CACHE_HIT = "Persistent compilation cache hit"


def say(msg: str) -> None:
    print(msg, flush=True)


class Leg:
    """One leg's record: its failed checks, wall time and compile stats."""

    def __init__(self, name: str):
        self.name = name
        self.failures: list[str] = []
        self.detail: dict = {}
        self.compile_s = 0.0
        self.compiles = 0
        self.slowest_compile: tuple[float, str] = (0.0, "")
        self.cache_hits = 0
        self._t0 = time.monotonic()
        self.wall_s = 0.0
        say(f"--- leg {name}")

    def check(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(f"    FAILED check [{self.name}]: {what}")
        return bool(ok)

    def read_compile_log(self, stderr_lines: list[str]) -> None:
        """Children run with JAX_LOG_COMPILES=1: sum JAX's own report of
        every XLA compilation, and count persistent-cache hits."""
        for line in stderr_lines:
            m = _XLA_COMPILE_RE.search(line)
            if m:
                self.compiles += 1
                self.compile_s += float(m.group(2))
                self.slowest_compile = max(
                    self.slowest_compile, (float(m.group(2)), m.group(1))
                )
            elif _CACHE_HIT in line:
                self.cache_hits += 1

    def close(self) -> "Leg":
        self.wall_s = time.monotonic() - self._t0
        return self

    @property
    def ok(self) -> bool:
        return not self.failures

    def summary(self) -> dict:
        return {
            "leg": self.name,
            "ok": self.ok,
            "wall_s": round(self.wall_s, 1),
            "compile_s": round(self.compile_s, 1),
            "run_s": round(self.wall_s - self.compile_s, 1),
            "compiles": self.compiles,
            "slowest_compile_s": round(self.slowest_compile[0], 1),
            "slowest_compile": self.slowest_compile[1],
            "cache_hits": self.cache_hits,
            **({"failed": self.failures} if self.failures else {}),
            **self.detail,
        }


class Child:
    """A child process whose stdout and stderr are drained on threads (a
    full pipe wedges the child).  ``kill_at`` is the monotonic deadline
    of the whole smoke."""

    def __init__(self, argv: list[str], env: dict, kill_at: float):
        self.kill_at = kill_at
        self.out: list[str] = []
        self.err: list[str] = []
        self._seen = threading.Condition()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        self._threads = [
            threading.Thread(target=self._drain, args=(s, into), daemon=True)
            for s, into in ((self.proc.stdout, self.out), (self.proc.stderr, self.err))
        ]
        for t in self._threads:
            t.start()

    def _drain(self, stream, into: list[str]) -> None:
        for line in stream:
            with self._seen:
                into.append(line.rstrip("\n"))
                self._seen.notify_all()

    def wait_for_line(self, needle: str) -> str | None:
        """Block until a stderr line contains ``needle``; None if the
        child exits or the smoke's deadline passes first."""
        at = 0
        with self._seen:
            while True:
                for line in self.err[at:]:
                    if needle in line:
                        return line
                at = len(self.err)
                left = self.kill_at - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    return None
                self._seen.wait(min(left, 1.0))

    def wait(self) -> int | None:
        """Exit code, or None after killing a child that outlived the
        smoke's deadline."""
        try:
            rc = self.proc.wait(max(self.kill_at - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            self.stop(signal.SIGKILL)
            return None
        for t in self._threads:
            t.join(5)
        return rc

    def stop(self, sig=signal.SIGTERM, grace: float = 30.0) -> int | None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        for t in self._threads:
            t.join(5)
        return self.proc.returncode

    def tail(self, n: int = 25) -> str:
        lines = [l for l in self.err if "Finished " not in l and "Compiling " not in l]
        return "\n".join("      | " + l for l in lines[-n:])


def run_to_exit(leg: Leg, argv: list[str], env: dict, kill_at: float) -> Child:
    """Run one child to its end inside ``leg``; a non-zero exit or a
    deadline kill is a failed check (with the end of its stderr shown)."""
    child = Child(argv, env, kill_at)
    try:
        rc = child.wait()
    finally:
        child.stop(signal.SIGKILL, grace=5)
    leg.read_compile_log(child.err)
    if not leg.check(rc == 0, f"child exit code {rc} ({' '.join(argv[1:4])})"):
        say(child.tail())
    return child


def last_json(lines: list[str]) -> dict | None:
    for line in reversed(lines):
        if line.startswith("{"):
            return json.loads(line)
    return None


# ---------------------------------------------------------------- HTTP ----


class Client:
    """JSON over HTTP to the local server; no request outlives the
    smoke's deadline."""

    def __init__(self, port: int, kill_at: float):
        self.base = f"http://127.0.0.1:{port}"
        self.kill_at = kill_at

    def _open(self, path: str, body: dict | None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        return urllib.request.urlopen(
            req, timeout=max(self.kill_at - time.monotonic(), 1.0)
        )

    def json(self, path: str, body: dict | None = None):
        """(status, parsed JSON or text) of one request."""
        try:
            with self._open(path, body) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        text = raw.decode(errors="replace")
        try:
            return status, json.loads(text)
        except ValueError:
            return status, text

    def stream(self, body: dict) -> list[dict]:
        """The ``data:`` events of one SSE /generate."""
        events = []
        with self._open("/generate", dict(body, stream=True)) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("data:"):
                    events.append(json.loads(line[5:]))
                    if events[-1].get("done") or events[-1].get("error"):
                        break
        return events


# ------------------------------------------------------------- children ---
# Everything under here runs in a child (``chip_smoke.py --child NAME``)
# and is the only code in this file that imports JAX.


def child_probe() -> None:
    import jax

    devices = jax.devices()
    print(json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "devices": [
            {"id": d.id, "coords": list(getattr(d, "coords", ()) or ())}
            for d in devices
        ],
    }), flush=True)


def child_oracle(path: str) -> None:
    """Teacher-forced float32 log-probabilities of the tokens the chip
    generated: one cache-free forward pass over prompt + generation per
    case, attention through ``mha_reference``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_device_plugin_tpu.models.transformer import GPTConfig, TransformerLM
    from k8s_device_plugin_tpu.ops.flash_attention import mha_reference

    assert jax.devices()[0].platform == "cpu", jax.devices()
    with open(path) as f:
        job = json.load(f)
    g = job["geometry"]
    # Exactly the config and parameters models/http_server.py main() builds.
    cfg = GPTConfig(
        vocab_size=g["vocab"],
        hidden_size=g["hidden"],
        num_layers=g["layers"],
        num_heads=g["heads"],
        intermediate_size=g["hidden"] * 3,
        max_seq=g["page_size"] * g["max_pages_per_seq"],
        num_kv_heads=g["kv_heads"],
    )
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
    )["params"]
    ref = TransformerLM(
        dataclasses.replace(cfg, dtype=jnp.float32), attention_fn=mha_reference
    )
    width = cfg.max_seq  # causal: padding past a case's end changes nothing

    @jax.jit
    def logprobs(ids):
        return jax.nn.log_softmax(ref.apply({"params": params}, ids), axis=-1)

    out = []
    for case in job["cases"]:
        prompt, tokens = case["prompt"], case["tokens"]
        seq = prompt + tokens
        ids = np.zeros((1, width), np.int32)
        ids[0, : len(seq)] = seq
        lp = np.asarray(logprobs(jnp.asarray(ids)))[0]
        # Row p-1+i predicts generated token i.
        rows = lp[len(prompt) - 1 : len(prompt) - 1 + len(tokens)]
        out.append({
            "name": case["name"],
            "ref_logprobs": [float(rows[i, t]) for i, t in enumerate(tokens)],
            "ref_top_first": float(rows[0].max()),
        })
    print(json.dumps({"cases": out}), flush=True)


def child_kernel() -> None:
    """The split-K paged kernel, compiled by Mosaic, against the gather
    path at the shipped pool geometry: float, int8 and int4 pools, at one
    split and at the tuning table's split count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_device_plugin_tpu.ops import tuning
    from k8s_device_plugin_tpu.ops.paged_attention import paged_attention
    from k8s_device_plugin_tpu.ops.quant import (
        dequantize_kv, dequantize_kv4, quantize_kv, quantize_kv4,
    )

    assert jax.default_backend() == "tpu", jax.devices()
    g = GEOMETRY
    batch, heads, kv, ps, mpp = SLOTS, g["heads"], g["kv_heads"], g["page_size"], g["max_pages_per_seq"]
    d = g["hidden"] // heads
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(ks[0], (batch, heads, d), jnp.bfloat16)
    kf = jax.random.normal(ks[1], (g["num_pages"], ps, kv, d), jnp.bfloat16)
    vf = jax.random.normal(ks[2], (g["num_pages"], ps, kv, d), jnp.bfloat16)
    # Scrambled pages, lengths on and around page boundaries up to a full row.
    lens_host = np.array([1, 15, 16, 17, 100, 255, 400, ps * mpp], np.int32)[:batch]
    perm = np.asarray(jax.random.permutation(ks[3], g["num_pages"] - 1)) + 1
    table_host = np.zeros((batch, mpp), np.int32)
    used = 0
    for b, n in enumerate(lens_host):
        need = -(-int(n) // ps)
        table_host[b, :need] = perm[used : used + need]
        used += need
    table, lens = jnp.asarray(table_host), jnp.asarray(lens_host)

    def gather(k, v):
        kr = k[table].reshape(batch, mpp * ps, kv, d).astype(jnp.float32)
        vr = v[table].reshape(batch, mpp * ps, kv, d).astype(jnp.float32)
        qg = q.astype(jnp.float32).reshape(batch, kv, heads // kv, d)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kr, precision="highest") * d ** -0.5
        live = jnp.arange(mpp * ps)[None, None, None, :] < lens[:, None, None, None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        return jnp.einsum("bhgk,bkhd->bhgd", p, vr, precision="highest").reshape(batch, heads, d)

    k8, sk8 = quantize_kv(kf)
    v8, sv8 = quantize_kv(vf)
    k4, sk4 = quantize_kv4(kf)
    v4, sv4 = quantize_kv4(vf)
    pools = {
        "float": (kf, vf, None, None, gather(kf, vf)),
        "int8": (k8, v8, sk8, sv8, gather(dequantize_kv(k8, sk8, jnp.float32), dequantize_kv(v8, sv8, jnp.float32))),
        "int4": (k4, v4, sk4, sv4, gather(dequantize_kv4(k4, sk4, jnp.float32), dequantize_kv4(v4, sv4, jnp.float32))),
    }
    table_splits = tuning.pick_num_splits(mpp)
    verdict = {}
    for name, (pk, pv, sk, sv, want) in pools.items():
        for splits in sorted({1, table_splits}):
            got = jax.jit(
                lambda q, pk, pv, sk, sv, splits=splits: paged_attention(
                    q, pk, pv, table, lens, scale_k=sk, scale_v=sv, num_splits=splits
                )
            )(q, pk, pv, sk, sv)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
            verdict[f"{name}/splits={splits}"] = round(err, 5)
    print(json.dumps({
        "generation": tuning.device_generation(),
        "table_splits": table_splits,
        "max_abs_err": verdict,
    }), flush=True)


def child_sharding(tp: int) -> None:
    """The serving engine on a ``tp`` mesh over every chip, built the way
    models/http_server.py main() builds it: the sharding lint, bytes in
    use on each device, and each device's coords beside the mesh order."""
    import jax
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.engine import ServingEngine
    from k8s_device_plugin_tpu.models.transformer import (
        GPTConfig, PagedConfig, TransformerLM,
    )
    from k8s_device_plugin_tpu.parallel.mesh import (
        chips_per_host_bounds, mesh_from_allocation, snake_order,
    )

    g = GEOMETRY
    cfg = GPTConfig(
        vocab_size=g["vocab"], hidden_size=g["hidden"], num_layers=g["layers"],
        num_heads=g["heads"], intermediate_size=g["hidden"] * 3,
        max_seq=g["page_size"] * g["max_pages_per_seq"], num_kv_heads=g["kv_heads"],
    )
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
    )["params"]
    mesh = mesh_from_allocation(tp)
    engine = ServingEngine(
        cfg, params,
        PagedConfig(g["page_size"], g["num_pages"], g["max_pages_per_seq"]),
        max_slots=SLOTS, mesh=mesh, decode_block=16, prefill_chunk=256,
        admission="optimistic", kv_retain=True, kv_host_cache_mb=512,
    )
    del params  # like main(): only the engine's sharded copy stays
    rng = random.Random(SEED)
    done = engine.run([([rng.randrange(g["vocab"]) for _ in range(100)], 8)])
    leaves = engine.assert_sharded()
    bounds = chips_per_host_bounds()
    print(json.dumps({
        "tp": engine.debug_state()["tp"],
        "sharded_leaves": leaves,
        "tokens": len(done[0].tokens),
        "bounds": bounds,
        "snake_order": snake_order(bounds) if bounds else None,
        "mesh_order": [
            {"id": d.id, "coords": list(d.coords)} for d in mesh.devices.flat
        ],
        "bytes_in_use": {
            str(d.id): d.memory_stats()["bytes_in_use"] for d in jax.devices()
        },
    }), flush=True)


# ------------------------------------------------------------------ legs ---


def leg_probe(kill_at: float) -> tuple[Leg, dict | None]:
    """Under the ambient environment, so that what JAX would have picked
    on its own is what gets named."""
    leg = Leg("probe")
    env = dict(os.environ, PYTHONPATH=ROOT)
    child = run_to_exit(leg, [sys.executable, __file__, "--child", "probe"], env, kill_at)
    facts = last_json(child.out) if leg.ok else None
    if facts is not None:
        say(f"    jax.devices(): {facts['count']} x {facts['kind']!r} on platform {facts['platform']!r}")
        leg.check(
            facts["platform"] == "tpu",
            f"JAX found platform {facts['platform']!r} ({facts['kind']!r}), not a TPU",
        )
    return leg.close(), facts


class PluginPeer:
    """The real daemon over the machine's own devfs/sysfs, with the fake
    kubelet as its peer; kept up for the whole run, like a DaemonSet pod."""

    def __init__(self, leg: Leg, facts: dict, kill_at: float):
        sys.path.insert(0, ROOT)
        from k8s_device_plugin_tpu.kubelet.api import pb
        from k8s_device_plugin_tpu.plugin import native
        from tests.fakes import FakeKubelet

        self.pb = pb
        self.alloc_env: dict[str, str] | None = None
        self.daemon: Child | None = None
        self.tmp = tempfile.mkdtemp(prefix="chip-smoke-plugin-")
        env = dict(os.environ, PYTHONPATH=ROOT)
        env.pop("TPU_PROBE_LIB", None)
        # libtpu_probe.so is git-ignored, so a checkout has none: build it
        # here from native/tpu_probe.c where a compiler exists.
        if shutil.which("cc") or shutil.which("gcc"):
            env["TPU_PROBE_LIB"] = native.build_probe_library(
                os.path.join(self.tmp, "libtpu_probe.so")
            )
            leg.detail["probe"] = "native (built from native/tpu_probe.c)"
        else:
            leg.detail["probe"] = "python (no C compiler on this machine)"
        say(f"    health probe path: {leg.detail['probe']}")
        plugin_dir = os.path.join(self.tmp, "device-plugins")
        os.mkdir(plugin_dir)
        self.kubelet = FakeKubelet(plugin_dir)
        self.kubelet.start()
        self.daemon = Child(
            [sys.executable, "-m", f"{PACKAGE}.plugin.cli", "--root", "/",
             "--plugin-dir", self.kubelet.plugin_dir, "--pulse", "1"],
            env, kill_at,
        )
        if not leg.check(self.kubelet.registered.wait(30), "daemon registered with the kubelet within 30 s"):
            say(self.daemon.tail())
            return
        devices = self.list_devices()
        healthy = [d.ID for d in devices if d.health == "Healthy"]
        say(f"    ListAndWatch: {[(d.ID, d.health) for d in devices]}")
        if not leg.check(
            len(healthy) == facts["count"],
            f"{len(healthy)} healthy google.com/tpu device(s) listed, JAX reported {facts['count']} chip(s)",
        ):
            say(self.daemon.tail())
            return
        resp = self.kubelet.plugin_stub().Allocate(
            pb.AllocateRequest(container_requests=[pb.ContainerAllocateRequest(devicesIDs=healthy)]),
            timeout=10,
        ).container_responses[0]
        nodes = [d.host_path for d in resp.devices]
        leg.detail["device_nodes"] = nodes
        leg.detail["alloc_env"] = dict(resp.envs)
        say(f"    Allocate: nodes {nodes} env {dict(resp.envs)}")
        leg.check(nodes, "Allocate returned device nodes")
        for node in nodes:
            leg.check(os.path.exists(node), f"allocated device node {node} exists")
        if leg.ok:
            self.alloc_env = dict(resp.envs)

    def list_devices(self):
        stream = self.kubelet.plugin_stub().ListAndWatch(self.pb.Empty(), timeout=10)
        try:
            return list(next(stream).devices)
        finally:
            stream.cancel()

    def close(self, leg: Leg, facts: dict) -> None:
        """After the chip legs: the chips the workloads used are still
        listed healthy, and SIGTERM ends the daemon with code 0."""
        try:
            if self.alloc_env is not None:
                healthy = [d.ID for d in self.list_devices() if d.health == "Healthy"]
                leg.check(
                    len(healthy) == facts["count"],
                    f"{len(healthy)} device(s) still healthy after the chip legs",
                )
                rc = self.daemon.stop(signal.SIGTERM)
                leg.check(rc == 0, f"daemon exit code {rc} on SIGTERM")
        finally:
            if self.daemon is not None:
                self.daemon.stop(signal.SIGKILL, grace=5)
            self.kubelet.stop()
            shutil.rmtree(self.tmp, ignore_errors=True)


def chip_env(alloc_env: dict[str, str]) -> dict[str, str]:
    """The environment of a chip child: exactly what ``Allocate`` returned
    in place of every ambient TPU_* variable (a pod has no others),
    JAX_PLATFORMS=tpu so that a libtpu that cannot start is an error, and
    JAX's own compile log for the compile seconds."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env.update(alloc_env)
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="tpu", JAX_LOG_COMPILES="1")
    return env


def make_prompt(rng: random.Random, length: int) -> list[int]:
    return [rng.randrange(GEOMETRY["vocab"]) for _ in range(length)]


def check_generation(leg: Leg, name: str, status, body) -> bool:
    """Asked length, tokens inside the vocabulary, finite log-probs <= 0."""
    if not leg.check(status == 200 and isinstance(body, dict), f"{name}: HTTP {status} {str(body)[:200]}"):
        return False
    tokens, lps = body.get("tokens") or [], body.get("logprobs") or []
    return all([
        leg.check(len(tokens) == MAX_NEW, f"{name}: {len(tokens)} tokens, asked {MAX_NEW}"),
        leg.check(all(0 <= t < GEOMETRY["vocab"] for t in tokens), f"{name}: token outside the vocabulary"),
        leg.check(len(lps) == len(tokens), f"{name}: {len(lps)} logprobs for {len(tokens)} tokens"),
        leg.check(all(math.isfinite(x) and x <= 0 for x in lps), f"{name}: a logprob is not finite or is > 0"),
    ])


@contextlib.contextmanager
def replica(leg: Leg, env: dict, kill_at: float, extra_flags: list[str]):
    """The shipped replica as a child process.  Yields a Client once the
    server has announced its port (None if it never does); on the way
    out SIGTERM must end it with code 0, and its compile log is read."""
    server = Child(
        [sys.executable, "-m", f"{PACKAGE}.models.http_server", *SERVE_FLAGS, *extra_flags],
        env, kill_at,
    )
    try:
        line = server.wait_for_line("serving on :")
        if leg.check(line is not None, f"server {' '.join(extra_flags)} announced its port"):
            yield Client(int(line.split("serving on :")[1].split()[0]), kill_at)
            rc = server.stop(signal.SIGTERM, grace=60)
            leg.check(rc == 0, f"server exit code {rc} on SIGTERM")
        else:
            yield None
    finally:
        server.stop(signal.SIGKILL, grace=5)
        leg.read_compile_log(server.err)
        if not leg.ok:
            say(server.tail())


def leg_serve(facts: dict, env: dict, kill_at: float, extra_flags: list[str]) -> tuple[Leg, list[dict]]:
    """The shipped replica through its normal entry point.  Returns the
    distinct (prompt, tokens, logprobs) cases for the oracle."""
    leg = Leg("serve")
    cases: list[dict] = []
    with replica(leg, env, kill_at, extra_flags) as http:
        if http is None:
            return leg.close(), cases
        leg.detail["startup_s"] = round(time.monotonic() - leg._t0, 1)

        _, state = http.json("/debug/state")
        engine = state.get("engine", {}) if isinstance(state, dict) else {}
        seen = {k: engine.get(k) for k in ("platform", "device_kind", "device_count")}
        say(f"    /debug/state backend: {seen}")
        leg.check(
            seen == {"platform": "tpu", "device_kind": facts["kind"], "device_count": facts["count"]},
            f"/debug/state names the backend the probe saw ({seen})",
        )
        if facts["count"] > 1:
            tp = engine.get("tp") or {}
            leg.detail["tp_devices"] = tp.get("devices")
            leg.check(
                tp.get("size") == facts["count"]
                and len(set(tp.get("devices") or [])) == facts["count"]
                and all("TPU" in d.upper() for d in tp.get("devices") or []),
                f"/debug/state tp block lists {facts['count']} distinct TPU devices ({tp})",
            )

        rng = random.Random(SEED)

        def generate(name: str, prompt: list[int]) -> dict | None:
            status, body = http.json(
                "/generate",
                {"prompt": prompt, "max_new_tokens": MAX_NEW, "logprobs": True},
            )
            if not check_generation(leg, name, status, body):
                return None
            return {"name": name, "prompt": prompt, "tokens": body["tokens"], "logprobs": body["logprobs"]}

        # 1. one warm-up request per prompt-length bucket (cold compiles)
        t0 = time.monotonic()
        for n in WARMUP_LENGTHS:
            t1 = time.monotonic()
            case = generate(f"warmup-{n}", make_prompt(rng, n))
            say(f"    warm-up prompt {n}: {time.monotonic() - t1:.1f}s")
            if case:
                cases.append(case)
        leg.detail["warmup_s"] = round(time.monotonic() - t0, 1)

        # 2. eight concurrent requests: every slot decodes together
        prompts = [make_prompt(rng, n) for n in CONCURRENT_LENGTHS]
        results: list[dict | None] = [None] * len(prompts)

        def one(i: int) -> None:
            results[i] = generate(f"concurrent-{CONCURRENT_LENGTHS[i]}", prompts[i])

        t0 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        leg.detail["concurrent_s"] = round(time.monotonic() - t0, 1)
        leg.check(all(results), f"all {len(prompts)} concurrent requests were answered")
        cases.extend(r for r in results if r)

        # 3. one SSE stream
        prompt = make_prompt(rng, STREAM_LENGTH)
        events = http.stream({"prompt": prompt, "max_new_tokens": MAX_NEW, "logprobs": True})
        toks = [e for e in events if "token" in e]
        done = events[-1] if events else {}
        body = {"tokens": [e["token"] for e in toks], "logprobs": [e.get("logprob") for e in toks]}
        if leg.check(done.get("done") is True, f"stream ended in a done event ({str(done)[:120]})") and \
                check_generation(leg, "stream", 200, body):
            leg.check([e["index"] for e in toks] == list(range(MAX_NEW)), "stream indexes are contiguous")
            leg.check(done.get("tokens") == body["tokens"], "stream tokens equal the done event's")
            cases.append({"name": "stream", "prompt": prompt, **body})

        # 4. one prompt twice in sequence: identical tokens, a retained hit
        prompt = make_prompt(rng, REPEAT_LENGTH)
        _, kv0 = http.json("/debug/kvcache")
        first = generate("repeat-1", prompt)
        second = generate("repeat-2", prompt)
        _, kv1 = http.json("/debug/kvcache")
        if first and second:
            leg.check(first["tokens"] == second["tokens"], "the repeated prompt returned identical tokens")
            hits = (kv1["hits"]["retained"] - kv0["hits"]["retained"]) if isinstance(kv1, dict) and isinstance(kv0, dict) else 0
            leg.detail["retained_hits"] = hits
            leg.check(hits > 0, f"/debug/kvcache shows a retained hit for the repeated prompt (+{hits})")
            cases.append(first)

        # 5. the replica is still whole
        _, prof = http.json("/debug/profile")
        steps = prof.get("steps", 0) if isinstance(prof, dict) else 0
        leg.detail["engine_steps"] = steps
        leg.check(steps > 0, f"/debug/profile shows steps > 0 ({steps})")
        status, health = http.json("/healthz")
        leg.check(status == 200 and "ok" in str(health), f"/healthz is ok at the end, not fenced ({status} {str(health)[:80]})")
    return leg.close(), cases


def leg_oracle(cases: list[dict], kill_at: float) -> Leg:
    leg = Leg("oracle")
    if not leg.check(cases, "the serve leg produced cases to check"):
        return leg.close()
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump({"geometry": GEOMETRY, "cases": [
            {k: c[k] for k in ("name", "prompt", "tokens")} for c in cases
        ]}, f)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_LOG_COMPILES="1")
    try:
        child = run_to_exit(leg, [sys.executable, __file__, "--child", "oracle", f.name], env, kill_at)
    finally:
        os.unlink(f.name)
    out = last_json(child.out) if leg.ok else None
    if out is None:
        return leg.close()
    worst = 0.0
    for case, ref in zip(cases, out["cases"]):
        diffs = [abs(a - b) for a, b in zip(case["logprobs"], ref["ref_logprobs"])]
        worst = max(worst, max(diffs))
        leg.check(
            max(diffs) <= LOGPROB_TOL,
            f"{case['name']}: chip log-probs within {LOGPROB_TOL} nats of the float32 reference "
            f"(first token {diffs[0]:.4f}, worst of {len(diffs)} {max(diffs):.4f})",
        )
        # The chip's greedy first token must be (all but) the oracle's best.
        gap = ref["ref_top_first"] - ref["ref_logprobs"][0]
        leg.check(
            gap <= LOGPROB_TOL,
            f"{case['name']}: chip's first token is {gap:.4f} nats below the reference's best",
        )
    leg.detail["cases"] = len(cases)
    leg.detail["max_logprob_diff"] = round(worst, 4)
    say(f"    {len(cases)} prompts x {MAX_NEW} tokens: max |chip - float32 reference| = {worst:.4f} nats (tolerance {LOGPROB_TOL})")
    return leg.close()


def leg_kernel(env: dict, kill_at: float) -> Leg:
    leg = Leg("kernel")
    child = run_to_exit(leg, [sys.executable, __file__, "--child", "kernel"], env, kill_at)
    out = last_json(child.out) if leg.ok else None
    if out is not None:
        leg.detail.update(out)
        say(f"    {out['generation']}: table splits {out['table_splits']}, max |kernel - gather| {out['max_abs_err']}")
        for name, err in out["max_abs_err"].items():
            leg.check(err <= KERNEL_TOL, f"paged kernel {name}: max abs err {err} <= {KERNEL_TOL}")
    if not leg.ok:
        return leg.close()
    # One server start with --use-kernel that answers one request.
    with replica(leg, env, kill_at, ["--use-kernel"]) as http:
        if http is not None:
            _, state = http.json("/debug/state")
            cfg = state["engine"]["config"] if isinstance(state, dict) else {}
            leg.check(cfg.get("kernel") is True, f"/debug/state config.kernel is true ({cfg.get('kernel')})")
            status, body = http.json(
                "/generate",
                {"prompt": make_prompt(random.Random(SEED), 100), "max_new_tokens": MAX_NEW, "logprobs": True},
            )
            check_generation(leg, "use-kernel", status, body)
    return leg.close()


def leg_train(name: str, facts: dict, env: dict, kill_at: float) -> Leg:
    leg = Leg(name)
    child = run_to_exit(
        leg, [sys.executable, "-m", f"{PACKAGE}.models.benchmark", *TRAIN_LEGS[name]], env, kill_at
    )
    rec = last_json(child.out) if leg.ok else None
    if rec is None:
        return leg.close()
    say(f"    record: {json.dumps(rec)}")
    leg.detail["record"] = {k: rec[k] for k in ("platform", "device_kind", "device_count", "chips") if k in rec}
    leg.check(
        (rec.get("platform"), rec.get("device_kind"), rec.get("device_count"), rec.get("chips"))
        == ("tpu", facts["kind"], facts["count"], facts["count"]),
        "the record names the platform, device_kind and chips the probe saw",
    )
    if "final_loss" in rec:
        # The steps run as one scanned program, so a non-finite loss at any
        # step poisons the parameters and with them the last loss.
        leg.check(
            rec["final_loss"] is not None and math.isfinite(rec["final_loss"]),
            f"loss is finite ({rec['final_loss']})",
        )
    leg.check(rec.get("throughput", 0) > 0, "the record reports a positive rate")
    return leg.close()


def leg_sharding(facts: dict, env: dict, kill_at: float) -> Leg:
    leg = Leg("sharding")
    n = facts["count"]
    child = run_to_exit(leg, [sys.executable, __file__, "--child", "sharding", str(n)], env, kill_at)
    out = last_json(child.out) if leg.ok else None
    if out is None:
        return leg.close()
    leg.detail.update(out)
    say(f"    {json.dumps(out)}")
    leg.check(out["sharded_leaves"] > 0, "engine.assert_sharded() passed")
    leg.check(
        out["tp"]["size"] == n and len(set(out["tp"]["devices"])) == n,
        f"the tp block lists {n} distinct devices",
    )
    used = list(out["bytes_in_use"].values())
    leg.check(len(used) == n and min(used) > 0, f"bytes in use is non-zero on all {n} devices")
    leg.check(max(used) <= 1.25 * min(used), f"bytes in use is of like size on all devices ({used})")
    # Neighbours on the tp axis are neighbours on the board.
    coords = [d["coords"] for d in out["mesh_order"]]
    hops = [sum(abs(a - b) for a, b in zip(p, q)) for p, q in zip(coords, coords[1:])]
    leg.check(all(h == 1 for h in hops), f"consecutive mesh devices are one ICI hop apart (coords {coords})")
    return leg.close()


def planned_legs(chips: int) -> list[str]:
    """The chip legs, in order, for a machine with ``chips`` chips."""
    legs = ["serve", "oracle", "kernel", *TRAIN_LEGS]
    if chips > 1:
        # Over several chips the benchmark runner shards its train step
        # with jit in_shardings, and jax 0.9.0 refuses a Mosaic kernel
        # under a multi-device jit ("Mosaic kernels cannot be automatically
        # partitioned. Please wrap the call in a shard_map.", seen on the
        # four-chip host, PR 21): the LM's flash kernel needs a shard_map
        # first (ROADMAP Speed 9).  ResNet-50 has no Pallas kernel.
        legs.remove("gpt")
        legs.append("sharding")
    return legs


def main() -> int:
    t_start = time.monotonic()
    kill_at = t_start + LIMIT_S
    if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
        print(f"chip_smoke.py: no {PACKAGE}/ beside it in {ROOT}; run it from a checkout", file=sys.stderr)
        return 2
    probe, facts = leg_probe(kill_at)
    if not probe.ok:
        print("chip_smoke.py: no TPU: no leg was run", file=sys.stderr)
        return 2
    legs = [probe]
    expected = ["probe", "plugin", *planned_legs(facts["count"])]

    plugin_leg = Leg("plugin")
    peer = PluginPeer(plugin_leg, facts, kill_at)
    legs.append(plugin_leg.close())
    try:
        if peer.alloc_env is not None:
            env = chip_env(peer.alloc_env)
            tp = ["--tp", str(facts["count"])] if facts["count"] > 1 else []
            cases: list[dict] = []
            for name in expected[2:]:
                if name == "serve":
                    leg, cases = leg_serve(facts, env, kill_at, tp)
                elif name == "oracle":
                    leg = leg_oracle(cases, kill_at)
                elif name == "kernel":
                    leg = leg_kernel(env, kill_at)
                elif name == "sharding":
                    leg = leg_sharding(facts, env, kill_at)
                else:
                    leg = leg_train(name, facts, env, kill_at)
                legs.append(leg)
    finally:
        peer.close(plugin_leg, facts)

    ran = [leg.name for leg in legs]
    ok = ran == expected and all(leg.ok for leg in legs)
    wall = time.monotonic() - t_start
    say("=== chip_smoke summary")
    say(f"platform: {facts['platform']}  device_kind: {facts['kind']}  chips: {facts['count']}")
    for leg in legs:
        s = leg.summary()
        say(
            f"leg {s['leg']:<11} {'passed' if s['ok'] else 'FAILED'}  wall {s['wall_s']:>6.1f}s  "
            f"compile {s['compile_s']:>6.1f}s ({s['compiles']} programs, slowest {s['slowest_compile_s']}s "
            f"{s['slowest_compile']}, {s['cache_hits']} cache hits)  rest {s['run_s']:>6.1f}s"
        )
    for name in expected:
        if name not in ran:
            say(f"leg {name:<11} DID NOT RUN")
    say(f"total wall {wall:.1f}s of {LIMIT_S + 50:.0f}s; compile {sum(l.compile_s for l in legs):.1f}s")
    say("legs: " + json.dumps([leg.summary() for leg in legs]))
    say(json.dumps({
        "ok": ok,
        "device": {"platform": facts["platform"], "kind": facts["kind"], "count": facts["count"]},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        {"probe": child_probe, "oracle": child_oracle, "kernel": child_kernel,
         "sharding": lambda n: child_sharding(int(n))}[sys.argv[2]](*sys.argv[3:])
        sys.exit(0)
    sys.exit(main())
