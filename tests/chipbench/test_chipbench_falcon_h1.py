"""The family ``falcon_h1`` (chipbench/families/, reference/) through the
harness at a toy size on the CPU: the tiny cell (``data/tiny-falcon-h1.json``,
``data/tiny-shortchat.json``) is ADDED to a temporary copy as files and
appended entries, rehearses to a valid line with ``correct`` true, reads
``gap_max`` over the limit under the int8 control and with the mixer dropped
from the reference, and the three metrics this configuration brought read
their numbers without importing JAX."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_helpers as helpers
from chipbench import cells, check_line, families

CONF, MIX = "tiny-falcon-h1", "tiny-shortchat"
CELL = f"{CONF}.{MIX}"
REAL = "falcon-h1-34b-d6.shortchat"
NEW_METRICS = ("state_share_of_decode_bytes", "prefill_chunk_ms", "cache_write_dispatches_per_request")
# The same replica judged by a reference that leaves the mixer out.
NO_MIXER = '''"""falcon_h1's replica, judged by its reference without the mixer."""

from chipbench import families
from chipbench.reference import falcon_h1 as ref

_whole = families.load("falcon_h1")
build, params_tree, request_flops, decode_step = _whole.build, _whole.params_tree, _whole.request_flops, _whole.decode_step


def served_gaps(conf, seed, cases, pad_to, control):
    return ref.served_gaps(conf, seed, cases, pad_to, control=control, drop_mixer=True)
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = helpers.make_copy(str(tmp_path_factory.mktemp("falcon_h1")))
    here = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(helpers.DATA, f"{CONF}.json")) as f:
        conf = json.load(f)
    shutil.copy(os.path.join(helpers.DATA, f"{MIX}.json"), os.path.join(here, "traffic"))
    with open(os.path.join(here, "families", "falcon_h1_no_mixer.py"), "w") as f:
        f.write(NO_MIXER)
    for name, body in ((CONF, conf), (f"{CONF}-no-mixer", dict(conf, family="falcon_h1_no_mixer"))):
        with open(os.path.join(here, "configs", f"{name}.json"), "w") as f:
            json.dump(body, f, indent=1)
        with open(os.path.join(here, "workloads", f"{name}.{MIX}.json"), "w") as f:
            json.dump({"config": name, "traffic": MIX}, f)
        bench["configs"].append({"name": name, "source": "tests", "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "CPU rehearsal of falcon_h1"})
        bench["workloads"].append({"name": f"{name}.{MIX}", "config": name, "traffic": MIX, "chips": 1,
                                   "why": "CPU rehearsal of falcon_h1"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if REAL in metric.get("workloads", []):
                metric["workloads"].append(f"{name}.{MIX}")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def last_line(proc, codes=(0,)):
    assert proc.returncode in codes, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert len(lines) == 1
    return lines[0], json.loads(lines[0])


def test_traced_rehearsal_reports_every_metric_of_the_cell(copy):
    text, line = last_line(helpers.run_cell(copy, CELL, "--rehearse", "cpu", trace=1))
    units = cells.load_cell(CELL, copy).units(True)
    assert set(NEW_METRICS) < set(units) and len(units) == 9
    assert check_line.problems(text, units, 1, True, "cpu") == []
    assert set(line["metrics"]) == set(units), sorted(set(units) - set(line["metrics"]))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line["compared"]
    got = {name: line["metrics"][name]["value"] for name in NEW_METRICS}
    assert 0 < got["state_share_of_decode_bytes"] < 100 and got["prefill_chunk_ms"] > 0
    # A graft and a teardown a request; the window's edges may hold one of a pair.
    assert 1.5 < got["cache_write_dispatches_per_request"] < 2.5


def test_the_control_reads_over_the_limit_and_the_program_under_it(copy):
    text, line = last_line(helpers.run_cell(copy, CELL, "--rehearse", "cpu", "--control", "1", seed=7), codes=(0, 4))
    assert check_line.problems(text, cells.load_cell(CELL, copy).units(False), 1, False, "cpu") == []
    gap = line["compared"]["gap_max"]
    assert line["correct"] is True and gap["value"] < gap["limit"] / 10, line["compared"]
    control = line["controls"]["control_int8"]["gap_max"]
    assert control["limit"] == gap["limit"] and control["value"] > control["limit"]
    assert line["control_correct"] == {"control_int8": False}


def test_the_reference_without_the_mixer_does_not_agree(copy):
    _, line = last_line(helpers.run_cell(copy, f"{CONF}-no-mixer.{MIX}", "--rehearse", "cpu"))
    gap = line["compared"]["gap_max"]
    assert line["correct"] is False and gap["value"] > 4 * gap["limit"], line["compared"]
    assert line["compared"]["wrong_length"]["value"] == 0 and line["failed"] == 0


IN_THE_COPY = r"""
import json, sys
from types import SimpleNamespace as NS
from chipbench import cells
assert "jax" not in sys.modules
cell = cells.load_cell(sys.argv[1])
results = [NS(prompt_tokens=20 + 3 * i, token_times=[99.0 + 0.4 * i + 0.1 * k for k in range(12 + i)]) for i in range(3)]
scrape = lambda writes, requests, **more: {"tpu_engine_cache_write_dispatches_total": writes, "tpu_engine_requests_total": requests, **more}
ctx = {"cell": cell, "window": (100.0, 2.0), "results": results, "slots": 4, "capture_interval": (100.0, 101.0),
       "device": {"kind": "TPU v5 lite", "count": 1},
       "scraped": {"before": scrape(10.0, 5.0), "after": scrape(34.0, 17.0, tpu_engine_slot_state_bytes=4 * 22528.0)},
       "trace_reduced": {"program_events": [["jit_run", 0.004, 1], ["jit_block", 0.5, 4], ["jit_run", 0.002, 1], ["jit_run_other", 0.009, 1]]}}
out = {name: cells.load_reader(name)(ctx) for name in sys.argv[2:]}
older = dict(ctx, scraped={"before": {}, "after": {"tpu_engine_requests_total": 17.0}}, trace_reduced=None)
out["older"] = [cells.load_reader(name)(older) for name in sys.argv[2:]]
assert "jax" not in sys.modules, "a reader runs in the parent, which never imports JAX"
print(json.dumps(out))
"""


def test_the_three_readers_read_their_numbers_without_jax(copy):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([copy, helpers.REPO]))
    proc = subprocess.run([sys.executable, "-c", IN_THE_COPY, CELL, *NEW_METRICS],
                          cwd=copy, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout)
    assert got["cache_write_dispatches_per_request"] == 2.0
    assert got["prefill_chunk_ms"] == pytest.approx(4.0)  # the median of 4, 2 and 9 ms
    # Two of the three requests stream at the capture's middle (100.5; the
    # first ended at 100.1): 2 x 22,528 bytes a live slot.
    family = families.load("falcon_h1")
    with open(os.path.join(helpers.DATA, f"{CONF}.json")) as f:
        m = json.load(f)
    contexts = [20 + 3 * i + sum(1 for k in range(12 + i) if 99.0 + 0.4 * i + 0.1 * k <= 100.5) for i in (1, 2)]
    scraped = {"slots": 4, "scraped": {"after": {"tpu_engine_slot_state_bytes": 4 * 22528.0}}}
    total = family.decode_step(m, contexts, scraped)[1]
    assert family.decode_state_bytes(m, contexts, scraped) == 2 * 2 * 22528
    assert family.decode_state_bytes(m, contexts, {}) == 2 * 2 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 2)  # from the shapes
    assert got["state_share_of_decode_bytes"] == pytest.approx(100.0 * 2 * 2 * 22528 / total)
    # A program without the gauge, the counter or a trace: nothing to read, nothing raised.
    assert got["older"] == [None, None, None]


def real():
    with open(os.path.join(helpers.REPO, "chipbench", "configs", "falcon-h1-34b-d6.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths_and_says_its_cuts():
    m = real()
    assert m["family"] == "falcon_h1" and m["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert set(m["reduced"]) == set(m["published"]) == set(m["why_reduced"])
    assert (m["published"]["num_hidden_layers"], m["num_hidden_layers"]) == (72, 6)
    assert m["max_position_embeddings"] == m["engine"]["page_size"] * m["engine"]["max_pages_per_seq"] == 1024
    widths = {"hidden_size": 5120, "intermediate_size": 21504, "num_attention_heads": 20, "num_key_value_heads": 4,
              "head_dim": 128, "vocab_size": 261120, "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
              "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128, "mamba_expand": 2,
              "mlp_expansion_factor": 8, "rope_theta": 100000000000, "rms_norm_eps": 1e-05}
    assert {k: m[k] for k in widths} == widths
    for key in ("slots", "num_pages", "ssm_state_dtype", "weights", "mamba_expand", "attn_layer_indices", "mamba_use_mlp"):
        assert key in m["assumed"]
    with open(os.path.join(helpers.REPO, "chipbench", "traffic", "shortchat.json")) as f:
        assert json.load(f)["clients"] == m["engine"]["slots"] == 32  # never more clients than slots


def test_the_counts_of_work_are_the_issues_reckoning():
    m, family = real(), families.load("falcon_h1")
    assert family.layer_matmul_params(m) == 31_457_280 + 47_349_760 + 20_971_520 + 330_301_440
    assert family.weight_bytes(m) == pytest.approx(7.84e9, rel=0.01)  # six layers and the head, bfloat16
    per_slot = family.state_bytes_per_slot(m, {})
    assert per_slot == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)  # 4 MiB of state a layer and the tail
    contexts = [400] * 32
    flops, nbytes = family.decode_step(m, contexts, {})
    state = family.decode_state_bytes(m, contexts, {})
    assert state == 2 * 32 * per_slot and 0.16 < state / nbytes < 0.18
    assert nbytes == family.weight_bytes(m) + 2 * 6 * 512 * 2 * 32 * 402 + state
    # The gauge, where the run scraped it, is what the state's bytes are counted from.
    ctx = {"slots": 32, "scraped": {"after": {"tpu_engine_slot_state_bytes": 32 * 1000.0}}}
    assert family.decode_state_bytes(m, contexts, ctx) == 2 * 32 * 1000.0
    token = family.token_flops(m, 0, False)
    assert token == 2.0 * 6 * family.layer_matmul_params(m) + 4.0 * 6 * (2560 + 32 * 128 * 256)
    assert family.request_flops(m, 3, 2) == sum(family.token_flops(m, p, p == 2) for p in range(3)) + family.token_flops(m, 3, True)


def test_a_program_without_the_mixer_is_refused_at_once(monkeypatch):
    from k8s_device_plugin_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class OlderConfig:
        vocab_size: int = 0
        hidden_size: int = 0

    monkeypatch.setattr(transformer, "GPTConfig", OlderConfig)
    m = real()
    with pytest.raises(SystemExit, match="no mixer"):
        families.load("falcon_h1").build(m, m["engine"])
