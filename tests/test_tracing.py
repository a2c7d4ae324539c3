"""Tracing subsystem + benchmark-runner gpt paths (CPU smoke)."""

from __future__ import annotations

import json
import logging
import os

import jax
import jax.numpy as jnp
import pytest

from k8s_device_plugin_tpu.utils import tracing


def test_trace_noop_without_dir():
    with tracing.trace(None):
        pass  # must be a cheap no-op


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "trace")
    with tracing.trace(d):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert files, "profiler produced no output"


@pytest.mark.parametrize("python_frames", [False, True])
def test_trace_runs_pythons_tracer_only_when_asked(tmp_path, python_frames):
    """The capture holds the host's TraceMe events either way (a
    TraceAnnotation among them); a ``$file:line fn`` event per Python
    call only with ``python_frames``."""
    from chipbench import trace as bench_trace

    def work():
        return jnp.ones((8, 8)).sum()

    with tracing.trace(str(tmp_path), python_frames=python_frames):
        with jax.profiler.TraceAnnotation("test-region"):
            work().block_until_ready()
    profile = bench_trace.load(bench_trace.find_xplane(str(tmp_path)))
    names = {e.name for p in profile.planes for ln in p.lines for e in ln.events}
    assert "test-region" in names
    assert any(n.startswith("$") for n in names) == python_frames


def test_timed_rpc_observes_and_logs(caplog):
    seen = []

    @tracing.timed_rpc(observe=seen.append)
    def handler(x):
        return x + 1

    assert handler(1) == 2
    assert len(seen) == 1 and seen[0] >= 0

    @tracing.timed_rpc(threshold_ms=0.0)
    def noisy():
        return "ok"

    with caplog.at_level(logging.DEBUG, logger="k8s_device_plugin_tpu.utils.tracing"):
        noisy()


def test_default_trace_dir_env():
    assert tracing.default_trace_dir({}) is None
    assert tracing.default_trace_dir({"TPU_PLUGIN_TRACE_DIR": "/x"}) == "/x"


def test_benchmark_gpt_train_smoke(capsys):
    from k8s_device_plugin_tpu.models import benchmark

    benchmark.main(
        [
            "--model", "gpt", "--tiny",
            "--batch-size", "8", "--seq-len", "16",
            "--steps", "2", "--warmup", "1", "--dp", "-1",
        ]
    )
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "gpt"
    assert out["throughput"] > 0


def test_benchmark_gpt_decode_smoke(capsys, tmp_path):
    from k8s_device_plugin_tpu.models import benchmark

    benchmark.main(
        [
            "--model", "gpt-decode", "--tiny",
            "--batch-size", "2", "--prompt-len", "4", "--decode-tokens", "8",
            "--trace-dir", str(tmp_path / "trace"),
        ]
    )
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "gpt-decode"
    assert out["new_tokens"] == 8
    assert out["throughput"] > 0
    assert os.path.isdir(tmp_path / "trace")


def test_benchmark_sampled_decode_smoke(capsys):
    from k8s_device_plugin_tpu.models import benchmark

    benchmark.main(
        [
            "--model", "gpt-decode", "--tiny",
            "--batch-size", "2", "--prompt-len", "4", "--decode-tokens", "6",
            "--temperature", "0.8", "--top-k", "16",
        ]
    )
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "gpt-decode"
    assert out["sampler"] == "temperature=0.8,top_k=16"
    assert out["throughput"] > 0


def test_benchmark_pipelined_1f1b_smoke(capsys):
    from k8s_device_plugin_tpu.models import benchmark

    benchmark.main(
        [
            "--model", "gpt", "--tiny",
            "--pp", "2", "--pp-schedule", "1f1b", "--n-micro", "2",
            "--batch-size", "4", "--seq-len", "16",
            "--steps", "2", "--warmup", "1",
        ]
    )
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "gpt-pp"
    assert out["schedule"] == "1f1b"
    assert out["throughput"] > 0


def test_timed_rpc_records_daemon_span():
    """timed_rpc routes each call into the span ring as a daemon-side
    span (DAEMON_TRACE) while the observe= metrics hook keeps firing —
    one tracing story, two entry points."""
    from k8s_device_plugin_tpu.utils.spans import DAEMON_TRACE, SpanRecorder

    rec = SpanRecorder()
    seen = []

    @tracing.timed_rpc(spans=rec, observe=seen.append)
    def Allocate():
        return "ok"

    assert Allocate() == "ok"
    assert Allocate() == "ok"
    spans = rec.snapshot()
    assert len(spans) == 2
    assert spans[0]["name"] == "rpc.Allocate"
    assert spans[0]["trace_id"] == DAEMON_TRACE
    assert spans[0]["duration_ms"] >= 0
    assert len(seen) == 2  # metrics hook intact alongside the span


def test_timed_rpc_late_bound_recorder():
    """spans= accepts a no-arg callable resolved per call: decoration at
    class-definition time, recorder wired later (or never)."""
    from k8s_device_plugin_tpu.utils.spans import SpanRecorder

    holder = {"rec": None}

    @tracing.timed_rpc(spans=lambda: holder["rec"])
    def handler():
        return 1

    handler()  # no recorder yet: silently unrecorded, no crash
    holder["rec"] = SpanRecorder()
    handler()
    assert len(holder["rec"].snapshot()) == 1
