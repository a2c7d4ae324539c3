"""Concurrency stress: RPCs, health polling, and stream interrupts at once.

The reference ships known races and no race detection (SURVEY.md §2.1 defect
list, §5.2: no -race in the build); this suite is the TPU build's answer —
hammer the servicer from many threads while the poller mutates state and
assert nothing deadlocks, crashes, or serves a torn snapshot.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent import futures

import grpc
import pytest

from k8s_device_plugin_tpu.kubelet.api import (
    DevicePluginStub,
    add_device_plugin_servicer,
    pb,
)
from k8s_device_plugin_tpu.plugin import discovery
from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
from k8s_device_plugin_tpu.plugin.server import TpuDevicePlugin

from fakes import make_fake_tpu_host

N_CHIPS = 4
THREADS = 8
DURATION_S = 3.0


@pytest.fixture()
def served_plugin(tmp_path, plugin_dir):
    root = make_fake_tpu_host(str(tmp_path / "host"), n_chips=N_CHIPS)
    plugin = TpuDevicePlugin(
        discover=lambda: discovery.discover(root=root, environ={}),
        health_checker=ChipHealthChecker(root=root),
    )
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=THREADS + 4))
    add_device_plugin_servicer(plugin, server)
    sock = os.path.join(plugin_dir, "plugin.sock")
    server.add_insecure_port(f"unix://{sock}")
    server.start()
    channel = grpc.insecure_channel(f"unix://{sock}")
    yield root, plugin, DevicePluginStub(channel)
    channel.close()
    server.stop(grace=None)


def test_concurrent_allocate_poll_and_health_flips(served_plugin):
    root, plugin, stub = served_plugin
    health_dir = os.path.join(root, "run/tpu/health")
    os.makedirs(health_dir, exist_ok=True)
    stop = threading.Event()
    errors: list = []
    latencies: list = []  # seconds per Allocate RPC, all threads (GIL-safe append)

    def allocator(i):
        req = pb.AllocateRequest(
            container_requests=[
                pb.ContainerAllocateRequest(devicesIDs=[f"tpu-{i % N_CHIPS}"])
            ]
        )
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                resp = stub.Allocate(req)
                latencies.append(time.perf_counter() - t0)
                car = resp.container_responses[0]
                # Snapshot consistency: env must name exactly the chip asked.
                assert car.envs["TPU_VISIBLE_CHIPS"] == str(i % N_CHIPS)
            except grpc.RpcError as e:
                # The flipper makes chips unhealthy; that rejection is the
                # CORRECT answer, anything else is a bug.
                latencies.append(time.perf_counter() - t0)
                if e.code() != grpc.StatusCode.FAILED_PRECONDITION:
                    errors.append(e)
            except Exception as e:  # noqa: BLE001 — collect for the assert
                errors.append(e)

    def poller():
        while not stop.is_set():
            try:
                plugin.poll_once()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    def flipper():
        i = 0
        while not stop.is_set():
            path = os.path.join(health_dir, f"accel{i % N_CHIPS}")
            try:
                if i % 2:
                    with open(path, "w") as f:
                        f.write("Unhealthy")
                elif os.path.exists(path):
                    os.unlink(path)
            except OSError as e:
                errors.append(e)
            i += 1
            time.sleep(0.002)

    def option_getter():
        while not stop.is_set():
            try:
                stub.GetDevicePluginOptions(pb.Empty())
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = (
        [threading.Thread(target=allocator, args=(i,)) for i in range(THREADS)]
        + [threading.Thread(target=poller) for _ in range(2)]
        + [threading.Thread(target=flipper), threading.Thread(target=option_getter)]
    )
    for t in threads:
        t.start()
    time.sleep(DURATION_S)
    stop.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "worker thread hung (deadlock)"
    assert not errors, errors[:3]
    # Allocation-latency budget (BASELINE.json secondary metric): p99 under
    # 50 ms even with pollers, health flips, and 8 allocator threads running
    # — the pod-startup path must never stall behind the health machinery.
    # Client-side wall clock over GIL-contended threads is noisy on shared
    # CI (measured ≈21 ms idle), so the budget is env-tunable for loaded
    # runners; the default stays the documented 50 ms contract.
    budget_ms = float(os.environ.get("ALLOCATE_P99_BUDGET_MS", "50"))
    assert len(latencies) > 100, "too few Allocate samples to judge latency"
    p99 = sorted(latencies)[int(len(latencies) * 0.99)]
    print(f"Allocate p99 under stress: {p99 * 1e3:.2f} ms over {len(latencies)} calls")
    assert p99 < budget_ms / 1e3, (
        f"Allocate p99 {p99*1e3:.1f} ms exceeds the {budget_ms:.0f} ms budget"
    )


def test_stream_survives_interrupt_storm(served_plugin):
    """ListAndWatch under rapid interrupt_streams + poll churn: the stream
    ends cleanly (epoch bump) rather than hanging or crashing."""
    root, plugin, stub = served_plugin
    stream = stub.ListAndWatch(pb.Empty())
    first = next(stream)
    assert len(first.devices) == N_CHIPS

    stop = threading.Event()

    def churner():
        while not stop.is_set():
            plugin.poll_once()
            time.sleep(0.001)

    t = threading.Thread(target=churner)
    t.start()
    time.sleep(0.3)
    plugin.interrupt_streams()
    # The stream must terminate (StopIteration) or yield updates then stop —
    # drain with a deadline.
    deadline = time.time() + 5
    try:
        while time.time() < deadline:
            next(stream)
    except StopIteration:
        pass
    except grpc.RpcError:
        pass  # server-side close surfaces as an RpcError on the client
    else:
        pytest.fail("stream did not terminate after interrupt_streams()")
    finally:
        stop.set()
        t.join(timeout=5)
