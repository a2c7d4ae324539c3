"""Record what the tests of the owner loop's readers keep
(tests/chipbench/data/): a short capture of a tiny replica under a few
requests (so the ``engine.<phase>`` annotations lie beside real device
operations), and the pair of ``/metrics`` scrapes around it.  Run on the chip; off it the capture holds the CPU's stand-in lines.

    python3 -m chipbench.tools.record_loop_trace <out.xplane.pb> <out-scrapes.json> [host tracer level, 1]
    python3 -m chipbench.tools.record_loop_trace --trim <recorded.xplane.pb> <kept.xplane.pb>

The second form (anywhere; it reads the file through TensorFlow's
``xplane_pb2``) cuts a recording to what the tests read, 2.7 MB to 0.4 MB:
the device's ``XLA Ops`` and ``XLA Modules`` lines and the owner thread's
host line; an operation's name without its HLO text and without its source
stack.  Each operation keeps the rest of its metadata: ``tf_op`` (the Flax
module or ``jax.named_scope`` it came from), ``source``, ``flops``,
``bytes_accessed``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

from .. import loadgen, run, trace


def trim(src: str, dst: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        names = plane.event_metadata
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name in (trace.OPS_LINE, trace.MODULES_LINE)]
        elif plane.name == "/host:CPU":
            lines = [ln for ln in plane.lines if any(names[e.metadata_id].name.startswith("engine.") for e in ln.events)]
        else:
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        kept.lines.extend(lines)
        stat_ids = {st.metadata_id for ln in lines for e in ln.events for st in e.stats}
        for key in {e.metadata_id for ln in lines for e in ln.events}:
            meta = kept.event_metadata[key]
            meta.CopyFrom(names[key])
            meta.name = meta.name.split(" = ")[0]
            meta.display_name = meta.display_name.split(" = ")[0]
            stats = [st for st in meta.stats if plane.stat_metadata[st.metadata_id].name != "source_stack"]
            del meta.stats[:]
            meta.stats.extend(stats)
            stat_ids |= {st.metadata_id for st in stats}
        for key in stat_ids & set(plane.stat_metadata):
            kept.stat_metadata[key].CopyFrom(plane.stat_metadata[key])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{src}: {os.path.getsize(src)} bytes -> {dst}: {os.path.getsize(dst)} bytes")


def main() -> None:
    if sys.argv[1] == "--trim":
        return trim(sys.argv[2], sys.argv[3])
    out_trace, out_scrapes = sys.argv[1], sys.argv[2]
    host_level = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    import jax
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
    from k8s_device_plugin_tpu.models.http_server import EngineServer
    from k8s_device_plugin_tpu.models.transformer import GPTConfig, PagedConfig, TransformerLM
    from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params, PagedConfig(page_size=4, num_pages=64, max_pages_per_seq=16),
        max_slots=2, metrics=EngineMetrics(registry), decode_block=4,
    )
    server = EngineServer(engine, host="127.0.0.1", port=0, registry=registry, enable_trace=True).start()
    port = server.port

    def generate(n: int) -> None:
        status, body = loadgen.post(port, "/generate", {"prompt": list(range(1, n + 1)), "max_new_tokens": 8}, timeout=300)
        if status != 200:
            raise SystemExit(f"/generate answered {status}: {body[:200]!r}")

    try:
        generate(8)  # compile before the capture
        before = run.scrape(port)
        # As POST /debug/trace captures (Python's tracer off), less what
        # would be most of the file: the programs' HLO text and the
        # runtime's own host events below the critical level.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = host_level
        opts.enable_hlo_proto = False
        tdir = tempfile.mkdtemp(prefix="chipbench-record-loop-")
        jax.profiler.start_trace(tdir, profiler_options=opts)
        for _ in range(2):  # one after the other: prefill, blocks, the tail, the teardown
            generate(8)
        time.sleep(0.12)  # an idle wait of the loop in the capture too
        jax.profiler.stop_trace()
        after = run.scrape(port)
    finally:
        server.stop()
    for path in (out_trace, out_scrapes):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    shutil.copy(trace.find_xplane(tdir), out_trace)
    shutil.rmtree(tdir, ignore_errors=True)
    keep = lambda s: {k: v for k, v in s.items() if k.startswith("tpu_engine_")}  # noqa: E731
    with open(out_scrapes, "w") as f:
        json.dump({"platform": jax.devices()[0].platform, "before": keep(before), "after": keep(after)}, f, indent=1)
    reduced = trace.reduce(trace.load(out_trace))
    print(f"{out_trace}: {os.path.getsize(out_trace)} bytes on {jax.devices()[0].platform}; "
          f"idle gaps {reduced and reduced['idle_gaps']}")


if __name__ == "__main__":
    main()
