"""One run of one cell:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX.  Set-up: the real plugin daemon against the
machine's own devfs/sysfs with a kubelet peer, ``ListAndWatch``, a timed
``Allocate`` of the cell's chips, then ONE child under exactly the returned
variables; the child makes weights or state from ``--seed`` and warms every
shape.  Then the window runs for ``--seconds``.  Every child is stopped and
reaped before the one result line is written with a single ``os.write``.

``--rehearse cpu`` (tests and rehearsals only) points the daemon at a
made-up host tree and the child at the CPU; the line then says ``cpu``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import shutil
import signal
import sys
import threading
import time

T_PROCESS = time.monotonic()

from . import check_line, loadgen, traffic  # noqa: E402
from .cells import BENCH_ROOT, Cell, load_cell, load_reader  # noqa: E402
from .plugin_peer import PluginPeer, chip_env, make_fake_host  # noqa: E402
from .procs import Child  # noqa: E402
from .stats import DRAIN_S, RunFault, capture_span, judged, percentile, token_gaps_ms  # noqa: E402

SETUP_LIMIT_S = 1100.0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def echo(child: Child, *starts: str) -> None:
    """The child's own few lines about its set-up, on the run's stderr."""
    for line in child.read_err().splitlines():
        if line.startswith(starts):
            say(f"  [{child.name}] {line[:2000]}")


def program_root() -> str:
    spec = importlib.util.find_spec("k8s_device_plugin_tpu")
    if spec is None or not spec.submodule_search_locations:
        raise RunFault("the program (k8s_device_plugin_tpu) is not importable from here")
    return os.path.dirname(list(spec.submodule_search_locations)[0])


def cache_dir(prog_root: str) -> str:
    """utils/platform.py's one rule, for the reference child too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(prog_root, ".jax_cache")


# ------------------------------------------------------------ /metrics ----


def parse_exposition(text: str) -> dict[str, float]:
    """Prometheus text to name -> value, label sets summed."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def scrape(port: int) -> dict[str, float]:
    status, body = loadgen.get(port, "/metrics")
    if status != 200:
        raise RunFault(f"GET /metrics answered {status}")
    return parse_exposition(body.decode())


class Poller(threading.Thread):
    """Samples /metrics through the window (gauges have no other history)."""

    def __init__(self, port: int, period_s: float):
        super().__init__(daemon=True)
        self.port, self.period_s = port, period_s
        self.samples: list[tuple[float, dict[str, float]]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.period_s):
            try:
                self.samples.append((time.monotonic(), scrape(self.port)))
            except (OSError, RunFault):
                continue

    def stop(self) -> None:
        self._halt.set()
        self.join(10)


# -------------------------------------------------------------- tracing ----


class Capture(threading.Thread):
    """``POST /debug/trace`` at the end of the window (``capture_span``);
    once more if the first capture shows no device operation."""

    def __init__(self, port: int, t0: float, seconds: float, keep: bool = False):
        super().__init__(daemon=True)
        self.port, self.t0, self.seconds, self.keep = port, t0, seconds, keep
        self.offset, self.length = capture_span(seconds)
        self.reduced: dict | None = None
        self.interval: tuple[float, float] | None = None
        self.notes: list[str] = []

    def _once(self) -> bool:
        from . import trace as trace_mod  # reads the trace with jax.profiler, no backend

        a = time.monotonic()
        status, body = loadgen.post(self.port, "/debug/trace", {"seconds": self.length}, timeout=120)
        b = time.monotonic()
        if status != 200:
            self.notes.append(f"/debug/trace answered {status}: {body[:200]!r}")
            return False
        tdir = json.loads(body)["trace_dir"]
        try:
            path = trace_mod.find_xplane(tdir)
            if path is None:
                self.notes.append(f"no .xplane.pb under {tdir}")
                return False
            reduced = trace_mod.reduce(trace_mod.load(path))
        finally:
            if not self.keep:
                shutil.rmtree(tdir, ignore_errors=True)
        if reduced is None:
            self.notes.append("the capture holds no device operation")
            return False
        # The reply comes after the trace is written out: the capture itself
        # is the first ``length`` seconds after the request.
        self.reduced, self.interval = reduced, (a, min(b, a + self.length))
        return True

    def run(self) -> None:
        time.sleep(max(self.t0 + self.offset - time.monotonic(), 0.0))
        if not self._once():
            self._once()  # the answers still out keep the device at work past the close


# -------------------------------------------------------------- serving ----


def warm_groups(cell: Cell) -> list[list[list[int]]]:
    """Admission groups that make the replica compile every program the
    window can need.  A prefill job is shaped by (group size rounded up to
    a power of two, length bucket); the graft's eager slices by (that size,
    bucket, prompt length), its pad and scatter by the prompt length.  So:
    for each bucket, for each power-of-two size up to the slots, groups of
    that size that hold every prompt length of the bucket.  The first group
    decodes through every decode-block size.  A mix whose arrivals cannot
    form the larger groups names the sizes it can (``warm_group_sizes``):
    each size left out is four programs less to load in every run."""
    eng = cell.config["engine"]
    max_len = eng["page_size"] * eng["max_pages_per_seq"]
    sizes = sorted(cell.traffic.get("warm_group_sizes") or
                   [1 << k for k in range(eng["slots"].bit_length())], reverse=True)
    by_bucket: dict[int, list[int]] = {}
    for n in traffic.warmup_lengths(cell.traffic):
        by_bucket.setdefault(min(1 << (n - 1).bit_length(), max_len), []).append(n)
    groups = []
    for bucket in sorted(by_bucket, reverse=True):
        lengths = by_bucket[bucket]
        for size in sizes:
            for at in range(0, len(lengths), size):
                new = 2 * eng["decode_block"] if not groups else 2
                groups.append([[lengths[(at + i) % len(lengths)], new] for i in range(size)])
    return groups


def make_requests(spec: dict, seed: int, seconds: float, vocab: int):
    """An open loop's schedule for the window, or a closed loop's endless source."""
    if spec["loop"] == "open":
        return traffic.generate(spec, seed, seconds, vocab)
    return traffic.stream(spec, seed, vocab)


def host_phases(port: int) -> dict[str, float]:
    """Lifetime seconds of each host phase of the engine's loop
    (``GET /debug/profile``): taken as a difference over the window and
    said on stderr, for PERF.md's "where the time goes"; no metric reads it."""
    status, body = loadgen.get(port, "/debug/profile")
    if status != 200:
        return {}
    snap = json.loads(body)
    return {"steps": snap["steps"], **{k: v["total_s"] for k, v in snap["phases"].items()}}


def serve_window(cell: Cell, args, port: int, t_window: float, requests) -> tuple[list, list, dict]:
    """Returns the results, the requests sent, and what was scraped."""
    spec = cell.traffic
    poller = Poller(port, 0.5)
    capture = Capture(port, t_window, args.seconds, bool(args.keep)) if args.trace else None
    before, phases0 = scrape(port), host_phases(port)
    poller.start()
    if capture:
        capture.start()
    if spec["loop"] == "open":
        results, sent = loadgen.run_open(port, requests, t_window, args.seconds, DRAIN_S), requests
    else:
        sent = []
        results = loadgen.run_closed(port, requests, spec["clients"], t_window, args.seconds, DRAIN_S, sent)
    poller.stop()
    after, phases1 = scrape(port), host_phases(port)
    say("host loop over the window and its drain: " + " ".join(
        f"{k}={phases1[k] - phases0.get(k, 0):.3f}" for k in phases1))
    if capture:
        capture.join(180)
    return results, sent, {"before": before, "after": after, "samples": poller.samples, "capture": capture}


def pick_sample(results, requests, seed: int, extra: int) -> list[dict]:
    """The longest finished request and ``extra`` more drawn from the seed."""
    by_index = {r.index: r for r in requests}
    finished = [r for r in results if r.done and r.error is None and r.tokens]
    if not finished:
        raise RunFault("no request finished: nothing to compare")
    finished.sort(key=lambda r: (-(r.prompt_tokens + len(r.tokens)), r.index))
    rng = random.Random(f"chipbench-sample-{seed}")
    chosen = [finished[0]] + rng.sample(finished[1:], min(extra, len(finished) - 1))
    return [
        {"index": r.index, "prompt": list(by_index[r.index].prompt), "tokens": list(r.tokens)}
        for r in chosen
    ]


def run_reference(cell: Cell, args, env: dict, run_dir: str, sample: list[dict], platform: str, prog_root: str) -> dict:
    spec = cell.traffic
    sample_path = os.path.join(run_dir, "sample.json")
    out_path = os.path.join(run_dir, "reference.json")
    with open(sample_path, "w") as f:
        json.dump({"cases": sample, "pad_to": spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"]}, f)
    child = Child(
        "reference",
        [sys.executable, "-m", "chipbench.ref_child", "--config", cell.config_path,
         "--seed", str(args.seed), "--sample", sample_path, "--out", out_path,
         "--platform", platform, "--cache-dir", cache_dir(prog_root),
         "--control", str(int(args.control))],
        env, run_dir, BENCH_ROOT,
    )
    try:
        rc = child.wait(600)
    finally:
        child.stop(signal.SIGKILL, grace=1)
    if rc != 0:
        raise RunFault(f"the reference child exited {rc}\n{child.tail()}")
    with open(out_path) as f:
        return json.load(f)


def start_replica(cell: Cell, args, env: dict, run_dir: str, platform: str, ctx: dict):
    """The replica under ``Allocate``'s variables, warmed: (child, port)."""
    vocab = cell.config["model"]["vocab_size"]
    warm_path = os.path.join(run_dir, "warm.json")
    with open(warm_path, "w") as f:
        json.dump(warm_groups(cell), f)
    argv = [sys.executable, "-m", "chipbench.serve_child", "--config", cell.config_path,
            "--seed", str(args.seed), "--run-dir", run_dir, "--platform", platform,
            "--chips", str(cell.chips), "--warm", warm_path]
    if args.fault:
        argv += ["--fault", args.fault]
    server = Child("serve", argv, env, run_dir, BENCH_ROOT)
    ctx["children"].append(server)
    line = server.wait_for_line("serving on :", T_PROCESS + SETUP_LIMIT_S)
    if line is None:
        raise RunFault(f"the replica never announced its port\n{server.tail()}")
    port = int(line.split("serving on :")[1].split()[0])
    echo(server, "backend:", "weights:", "warm-up", "child set-up")
    # The HTTP path itself, once streamed (no program is new to the engine).
    warm = traffic.Request(-1, 0.0, tuple(random.Random(1).randrange(vocab) for _ in range(traffic.warmup_lengths(cell.traffic)[-1])), 4)
    for _ in range(2):
        res = loadgen.Result(-1, time.monotonic())
        loadgen.generate_once(port, warm, res, 300)
        if not res.done:
            raise RunFault(f"the warm-up request failed: {res.error}\n{server.tail()}")
    return server, port


def run_serve(cell: Cell, args, env: dict, run_dir: str, platform: str, prog_root: str, ctx: dict) -> dict:
    eng = cell.config["engine"]
    requests = make_requests(cell.traffic, args.seed, args.seconds, cell.config["model"]["vocab_size"])
    server, port = start_replica(cell, args, env, run_dir, platform, ctx)
    t_window = time.monotonic()
    t_window_wall = time.time()
    setup_s = t_window - T_PROCESS
    say(f"set-up {setup_s:.1f} s; window of {args.seconds} s")
    results, requests, scraped = serve_window(cell, args, port, t_window, requests)
    t_close_wall = time.time()
    say_tails(results)
    rc = server.stop(signal.SIGTERM, grace=60)
    echo(server, "memory_stats")
    exit_path = os.path.join(run_dir, "serve_exit.json")
    if rc != 0 or not os.path.exists(exit_path):
        raise RunFault(f"the replica exited {rc} without its exit record\n{server.tail()}")
    with open(exit_path) as f:
        device = json.load(f)
    sample = pick_sample(results, requests, args.seed, extra=cell.config["correct"]["sample_extra"])
    ref = run_reference(cell, args, env, run_dir, sample, platform, prog_root)
    ctx.update(
        results=results, requests=requests, window=(t_window, args.seconds),
        window_wall=(t_window_wall, t_close_wall), scraped=scraped, setup_s=setup_s,
        device=device, serve_err=server.err_path, slots=eng["slots"],
    )
    compared, controls = serve_compared(cell, results, sample, ref)
    return {"compared": compared, "controls": controls, "attempted": len(results),
            "failed": sum(1 for r in results if not r.done or r.error is not None)}


def say_tails(results) -> None:
    """The window's latencies on stderr, for PERF.md; no metric reads this."""
    ttft = [(r.token_times[0] - r.due) * 1e3 for r in results if r.token_times]
    for name, values in (("ttft_ms", ttft), ("itl_ms", token_gaps_ms(results))):
        if values:
            say(f"{name}: n={len(values)} mean={sum(values) / len(values):.1f} " + " ".join(
                f"p{q}={percentile(values, q):.1f}" for q in (50, 75, 80, 90, 95, 99)) + f" max={max(values):.1f}")


def serve_compared(cell: Cell, results, sample, ref: dict) -> tuple[dict, dict]:
    """Each number compared beside its limit; and, where the reference read
    the lower-precision control too (``--control 1``), the control's number
    beside the same limit."""
    limits = cell.config["correct"]
    gaps = [g for row in ref["rows"] for g in row["gaps"]]
    short = [r for r in results if r.done and len(r.tokens) != r.max_new_tokens]
    for r in short[:8]:
        say(f"wrong length: request {r.index}, prompt {r.prompt_tokens}, asked {r.max_new_tokens}, "
            f"got {len(r.tokens)} ({len(r.token_times)} streamed)")
    out = {
        "gap_max": {"value": max(gaps), "limit": limits["gap_max"]},
        "wrong_length": {"value": len(short), "limit": 0},
        "tokens_compared": {"value": len(gaps), "limit": None},
        "reference_s": {"value": ref["seconds"], "limit": None},
    }
    controls = {}
    if "control_gaps" in ref["rows"][0]:
        controls["control_int8"] = {"gap_max": {
            "value": max(g for row in ref["rows"] for g in row["control_gaps"]), "limit": limits["gap_max"],
        }}
    return out, controls


# ------------------------------------------------------------- training ----


def run_train(cell: Cell, args, env: dict, run_dir: str, platform: str, prog_root: str, ctx: dict) -> dict:
    out_path = os.path.join(run_dir, "train.json")
    argv = [sys.executable, "-m", "chipbench.train_child", "--config", cell.config_path,
            "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--run-dir", run_dir, "--platform", platform, "--chips", str(cell.chips),
            "--out", out_path, "--t-process", repr(T_PROCESS), "--control", str(int(args.control))]
    if args.fault:
        argv += ["--fault", args.fault]
    child = Child("train", argv, env, run_dir, BENCH_ROOT)
    ctx["children"].append(child)
    rc = child.wait(SETUP_LIMIT_S + args.seconds + 300)
    child.stop(signal.SIGKILL, grace=1)
    echo(child, "train child:")
    if rc != 0 or not os.path.exists(out_path):
        raise RunFault(f"the training child exited {rc}\n{child.tail()}")
    with open(out_path) as f:
        got = json.load(f)
    ctx.update(train=got, setup_s=got["setup_s"], device=got["device"], window=(None, args.seconds),
               trace_reduced=got.get("trace"), trace_notes=got.get("trace_notes", []))
    return {"compared": got["compared"], "controls": got.get("controls", {}), "attempted": got["steps"], "failed": 0}


# ----------------------------------------------------------------- main ----


def build_line(cell: Cell, args, ctx: dict, outcome: dict, platform: str) -> str:
    traced = bool(args.trace)
    metrics = {}
    for name, entry in (cell.per_layer if traced else cell.end_to_end).items():
        value = load_reader(name)(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    device = {k: ctx["device"][k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    line = {"attempted": outcome["attempted"], "failed": outcome["failed"], "metrics": metrics, "device": device}
    if traced:
        reduced = ctx.get("trace_reduced")
        if reduced is None:
            raise RunFault("traced run without a trace that holds device operations: " + "; ".join(ctx.get("trace_notes", [])))
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10], "idle_gaps": reduced["idle_gaps"][:10]}
    compared, controls = outcome["compared"], outcome.get("controls") or {}
    if controls:
        # The control and each planted fault through the same limits and the
        # same test as the program's numbers: each has to read False.
        line["control_correct"] = {name: judged(numbers) for name, numbers in controls.items()}
        line["controls"] = controls
    line = {"correct": judged(compared), **line, "compared": compared}
    text = json.dumps(line, allow_nan=False)
    wrong = check_line.problems(text, cell.units(traced), cell.chips, traced, platform)
    if wrong:
        raise RunFault("the result line breaks the contract: " + "; ".join(wrong))
    return text


def launch(args, ctx: dict) -> tuple:
    """Set-up as far as the child's environment: the cell's files, the run
    directory, the real plugin daemon with its kubelet peer, a timed
    ``Allocate``.  What it starts goes into ``ctx`` for ``teardown``."""
    platform = args.rehearse or "tpu"
    run_dir = os.path.join(BENCH_ROOT, ".chipbench_runs", f"{args.workload}-s{args.seed}-t{args.trace}")
    cell = load_cell(args.workload)
    prog_root = program_root()
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(os.path.join(run_dir, "tmp"))
    ctx["run_dir"] = run_dir
    host_root = "/"
    if args.rehearse:
        host_root = make_fake_host(os.path.join(run_dir, "host"), cell.chips)
    ctx["peer"] = peer = PluginPeer(run_dir, prog_root, host_root)
    alloc_env, allocate_ms, nodes = peer.allocate(cell.chips)
    say(f"Allocate ({allocate_ms:.1f} ms): nodes {nodes} env {alloc_env}")
    env = chip_env(alloc_env, prog_root, BENCH_ROOT, platform)
    env["TMPDIR"] = os.path.join(run_dir, "tmp")
    ctx.update(cell=cell, allocate_ms=allocate_ms, args=args)
    return cell, env, run_dir, platform, prog_root


def teardown(ctx: dict, keep: bool) -> None:
    """Every child stopped and reaped, then the daemon and its peer."""
    for child in ctx["children"]:
        child.stop(signal.SIGTERM, grace=30)
    if ctx.get("peer") is not None:
        ctx["peer"].close()
    if not keep and ctx.get("run_dir"):
        shutil.rmtree(ctx["run_dir"], ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chipbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], required=True)
    p.add_argument("--rehearse", choices=["cpu"], default=None)
    p.add_argument("--control", type=int, default=0, help="also read the lower-precision control (and a training cell's planted faults) and judge each by the cell's limits: control_correct in the line, exit 4 if one passes (the driver's runs never ask)")
    p.add_argument("--fault", default="", help="tests only: break the timed path")
    p.add_argument("--keep", type=int, default=0, help="keep the run directory")
    args = p.parse_args(argv)
    ctx: dict = {"children": []}
    text, last_words, passed_control = None, [], False
    try:
        cell, env, run_dir, platform, prog_root = launch(args, ctx)
        runner = {"serve": run_serve, "train": run_train}[cell.kind]
        outcome = runner(cell, args, env, run_dir, platform, prog_root, ctx)
        capture = (ctx.get("scraped") or {}).get("capture")
        if capture is not None:
            ctx["trace_reduced"], ctx["trace_notes"], ctx["capture_interval"] = capture.reduced, capture.notes, capture.interval
            for note in capture.notes:
                say(f"trace: {note}")
        text = build_line(cell, args, ctx, outcome, platform)
        for name, numbers in (outcome.get("controls") or {}).items():
            last_words.append(f"{name}: correct {judged(numbers)}: " + ", ".join(
                f"{n} = {c['value']} (limit {c['limit']})" for n, c in numbers.items()))
            passed_control = passed_control or judged(numbers)
        last_words += [f"compared {n} = {c['value']} (limit {c['limit']})" for n, c in outcome["compared"].items()]
    except (RunFault, RuntimeError, KeyError, OSError, ValueError) as e:
        say(f"chipbench.run: no result: {type(e).__name__}: {e}")
        text = None
    finally:
        teardown(ctx, bool(args.keep))
    if text is None:
        return 1
    for words in last_words:
        say(words)
    sys.stderr.flush()
    os.write(1, (text + "\n").encode())
    if passed_control:
        say("chipbench.run: a control or a planted fault came out correct: the limits do not catch it")
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
