"""The family ``solar_open2`` (chipbench/families/, reference/) through the
harness at a toy size on the CPU: the tiny cell (``data/tiny-solar-open2.json``
under ``data/tiny-shortchat.json``) is ADDED to a temporary copy as files and
appended entries, rehearses to a valid line with ``correct`` true and every
metric of the cell, reads ``gap_max`` over the limit under the int8 control
and with the recurrence or the softmax attention dropped from the
reference, and the readers this configuration reports read their numbers
without importing JAX."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_helpers as helpers
from chipbench import cells, check_line, families

CONF, MIX = "tiny-solar-open2", "tiny-shortchat"
CELL = f"{CONF}.{MIX}"
REAL = "solar-open2-d4e40.reasoning"
# Read off the device's trace of the recurrence kernel, which a CPU
# backend does not run (the jnp lane): the tiny cell does not report it.
DEVICE_ONLY = ("kda_decode_roofline",)
READERS = ("moe_dropped_assignments", "moe_peak_over_mean_load", "expert_share_of_decode_bytes",
           "state_share_of_decode_bytes", "kda_decode_roofline")
WITHOUT = '''"""solar_open2's replica, judged by its reference without {part}."""

from chipbench import families
from chipbench.reference import solar_open2 as ref

_whole = families.load("solar_open2")
build, params_tree, request_flops, decode_step = _whole.build, _whole.params_tree, _whole.request_flops, _whole.decode_step


def served_gaps(conf, seed, cases, pad_to, control):
    return ref.served_gaps(conf, seed, cases, pad_to, control=control, drop="{part}")
'''
DROPS = ("recurrence", "attention")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = helpers.make_copy(str(tmp_path_factory.mktemp("solar_open2")))
    here = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(helpers.DATA, f"{CONF}.json")) as f:
        conf = json.load(f)
    shutil.copy(os.path.join(helpers.DATA, f"{MIX}.json"), os.path.join(here, "traffic"))
    variants = [(CONF, conf)]
    for part in DROPS:
        with open(os.path.join(here, "families", f"solar_open2_no_{part}.py"), "w") as f:
            f.write(WITHOUT.format(part=part))
        variants.append((f"{CONF}-no-{part}", dict(conf, family=f"solar_open2_no_{part}")))
    for name, body in variants:
        with open(os.path.join(here, "configs", f"{name}.json"), "w") as f:
            json.dump(body, f, indent=1)
        with open(os.path.join(here, "workloads", f"{name}.{MIX}.json"), "w") as f:
            json.dump({"config": name, "traffic": MIX}, f)
        bench["configs"].append({"name": name, "source": "tests", "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "CPU rehearsal of solar_open2"})
        bench["workloads"].append({"name": f"{name}.{MIX}", "config": name, "traffic": MIX, "chips": 1,
                                   "why": "CPU rehearsal of solar_open2"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if REAL in metric.get("workloads", []) and metric["name"] not in DEVICE_ONLY:
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
    real = cells.load_cell(REAL, helpers.REPO).units(True)
    assert set(units) == set(real) - set(DEVICE_ONLY) and len(real) == 13
    assert check_line.problems(text, units, 1, True, "cpu") == []
    assert set(line["metrics"]) == set(units), sorted(set(units) - set(line["metrics"]))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line["compared"]
    got = {name: line["metrics"][name]["value"] for name in READERS if name in units}
    assert got["moe_dropped_assignments"] == 0 and got["moe_peak_over_mean_load"] >= 1
    assert 0 < got["expert_share_of_decode_bytes"] < 100 and 0 < got["state_share_of_decode_bytes"] < 100
    # A graft and a teardown a request; the window's edges may hold one of a pair.
    assert 1.5 < line["metrics"]["cache_write_dispatches_per_request"]["value"] < 2.5


def test_the_control_reads_over_the_limit_and_the_program_under_it(copy):
    text, line = last_line(helpers.run_cell(copy, CELL, "--rehearse", "cpu", "--control", "1", seed=7), codes=(0, 4))
    assert check_line.problems(text, cells.load_cell(CELL, copy).units(False), 1, False, "cpu") == []
    gap = line["compared"]["gap_max"]
    assert line["correct"] is True and gap["value"] < gap["limit"] / 10, line["compared"]
    control = line["controls"]["control_int8"]["gap_max"]
    assert control["limit"] == gap["limit"] and control["value"] > control["limit"]
    assert line["control_correct"] == {"control_int8": False}


@pytest.mark.parametrize("part", DROPS)
def test_the_reference_without_a_part_does_not_agree(copy, part):
    _, line = last_line(helpers.run_cell(copy, f"{CONF}-no-{part}.{MIX}", "--rehearse", "cpu"))
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
def scrape(held, touched, steps, **more):
    return {"tpu_engine_moe_assignments_total": 8 * held, "tpu_engine_moe_expert_tokens_total": held,
            "tpu_engine_moe_decode_experts_touched_total": touched,
            "tpu_engine_moe_decode_layer_steps_total": steps, "tpu_engine_moe_dropped_assignments_total": 0.0, **more}
after = scrape(450.0, 100.0, 50.0, tpu_engine_moe_expert_tokens_peak=30.0, tpu_engine_cache_bytes_per_token=256.0,
               tpu_engine_slot_state_bytes=4 * 76800.0)
trace = {"op_seconds": {"%kda_decode": 0.002, "%kda_decode.1": 0.001, "%fusion.3": 0.5},
         "program_events": [["jit_block", 0.04, 16], ["jit_step", 0.003, 1], ["jit_run", 0.01, 1]]}
ctx = {"cell": cell, "window": (100.0, 2.0), "results": results, "slots": 4, "capture_interval": (100.0, 101.0),
       "device": {"kind": "TPU v5 lite", "count": 1}, "trace_reduced": trace,
       "scraped": {"before": scrape(150.0, 40.0, 20.0), "after": after,
                   "samples": [(100.5, {"tpu_engine_active_slots": 3.0}), (101.0, {"tpu_engine_active_slots": 4.0}),
                               (99.0, {"tpu_engine_active_slots": 1.0})]}}
out = {name: cells.load_reader(name)(ctx) for name in sys.argv[2:]}
older = dict(ctx, trace_reduced={"op_seconds": {"%fusion.3": 0.5}, "program_events": trace["program_events"]},
             scraped={"before": {}, "after": {"tpu_engine_requests_total": 17.0}, "samples": []})
out["older"] = [cells.load_reader(name)(older) for name in sys.argv[2:]]
assert "jax" not in sys.modules, "a reader runs in the parent, which never imports JAX"
print(json.dumps(out))
"""


def test_the_readers_read_their_numbers_without_jax(copy):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([copy, helpers.REPO]))
    proc = subprocess.run([sys.executable, "-c", IN_THE_COPY, REAL, *READERS],
                          cwd=copy, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout)
    family, m = families.load("solar_open2"), real()
    assert got["moe_dropped_assignments"] == 0.0
    # 450 tokens over 4 expert layers x 40 held experts.
    assert got["moe_peak_over_mean_load"] == pytest.approx(30.0 / (450.0 / 160))
    contexts = [20 + 3 * i + sum(1 for k in range(12 + i) if 99.0 + 0.4 * i + 0.1 * k <= 100.5) for i in (1, 2)]
    ctx = {"slots": 4, "scraped": {"before": {"tpu_engine_moe_decode_experts_touched_total": 40.0,
                                              "tpu_engine_moe_decode_layer_steps_total": 20.0},
                                   "after": {"tpu_engine_moe_decode_experts_touched_total": 100.0,
                                             "tpu_engine_moe_decode_layer_steps_total": 50.0,
                                             "tpu_engine_cache_bytes_per_token": 256.0,
                                             "tpu_engine_slot_state_bytes": 4 * 76800.0}}}
    total = family.decode_step(m, contexts, ctx)[1]
    assert family.touched_experts(m, contexts, ctx) == 2.0
    assert family.decode_state_bytes(m, contexts, ctx) == 2 * 76800.0 * 2
    assert got["expert_share_of_decode_bytes"] == pytest.approx(100.0 * family.decode_expert_bytes(m, contexts, ctx) / total)
    assert got["state_share_of_decode_bytes"] == pytest.approx(100.0 * family.decode_state_bytes(m, contexts, ctx) / total)
    # 17 steps x 3 linear layers, 3.5 slots active in the window, 3 ms of kda_decode.
    least = 51 * family.kda_decode_bytes(m, 3.5) / 819e9
    assert got["kda_decode_roofline"] == pytest.approx(100.0 * least / 0.003)
    # A program without the counters, the gauge or the kernel: nothing to read, nothing raised.
    assert got["older"] == [None] * len(READERS)


def real():
    with open(os.path.join(helpers.REPO, "chipbench", "configs", "solar-open2-d4e40.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths_and_says_its_cuts():
    m = real()
    cuts = ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert m["family"] == "solar_open2" and m["reduced"] == cuts
    assert set(cuts) == set(m["published"]) == set(m["why_reduced"])
    assert m["published"] == {"num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
                              "max_position_embeddings": 1048576}
    assert [m[k] for k in cuts] == [4, 40, 24576, 2048]
    assert m["max_position_embeddings"] == m["engine"]["page_size"] * m["engine"]["max_pages_per_seq"]
    with open(os.path.join(helpers.REPO, "tests", "chipbench", "data", "solar-open2-published.json")) as f:
        row = json.load(f)  # the published config.json, as the source gives it
    assert m["source"] == row["source_url"]
    assert {k: m[k] for k in row["config"] if k not in cuts} == {k: v for k, v in row["config"].items() if k not in cuts}
    dep = m["deployment"]
    assert dep["chips_sharing_a_layer"] * m["n_routed_experts"] == m["published"]["n_routed_experts"]
    assert dep["held_experts"] == list(range(40)) and dep["vocabulary_slices"] * m["vocab_size"] == 196608
    assert dep["pipeline_stages"] * dep["layers_per_stage"] == 48 and "12.8" in dep["expert_load"]
    for key in ("torch_dtype", "router_scoring", "low_rank", "attention_gate", "qk_norm", "l2norm_eps", "weights",
                "e_score_correction_bias", "linear_attention", "slots", "num_pages", "gap_max"):
        assert key in m["assumed"]
    with open(os.path.join(helpers.REPO, "chipbench", "traffic", "reasoning.json")) as f:
        assert json.load(f)["clients"] == m["engine"]["slots"] == 64  # never more clients than slots


def test_the_counts_of_work_match_the_hand_reckoning():
    m, family = real(), families.load("solar_open2")
    assert family.linear_params(m) == pytest.approx(137.7e6, rel=0.001)
    assert family.attention_params(m) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert family.moe_dense_params(m) + family.linear_params(m) == pytest.approx(154.8e6, rel=0.001)
    assert family.expert_params(m) == 3 * 4096 * 1280
    held = 4 * 40 * family.expert_params(m)
    outside = 3 * family.linear_params(m) + family.attention_params(m) + 4 * family.moe_dense_params(m)
    assert held == pytest.approx(2516.6e6, rel=0.001)
    assert 2 * (outside + held + 2 * 24576 * 4096) == pytest.approx(6.62e9, rel=0.005)
    assert family.state_bytes_per_slot(m, {}) == 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert family.state_bytes_per_slot(m, {}) == pytest.approx(13.0e6, rel=0.01)
    assert family.cache_bytes_per_token(m, {}) == 4096
    contexts = [750] * 64
    flops, nbytes = family.decode_step(m, contexts, {})
    # Without the run's counters, uniform routing's expectation: about 32 of 40 held experts a layer.
    assert family.touched_experts(m, contexts, {}) == pytest.approx(40 * (1 - (1 - 8 / 320) ** 64))
    experts, state = family.decode_expert_bytes(m, contexts, {}), family.decode_state_bytes(m, contexts, {})
    assert experts == pytest.approx(4.04e9, rel=0.01) and state == pytest.approx(1.67e9, rel=0.01)
    assert nbytes == family.dense_weight_bytes(m) + experts + state + 4096 * 64 * 751
    assert nbytes / 819e9 == pytest.approx(8.9e-3, rel=0.03)  # the least time of a step
    # One kernel call a layer: 64 slots of 64 heads of 128 x 128 float32, read and written.
    assert family.kda_decode_bytes(m, 64) == 64 * 4 * (2 * 64 * 128 * 128 + 5 * 64 * 128 + 64)
    assert family.linear_layers(m) == 3
    token = family.token_flops(m, 0, False)
    per_layer = family.moe_dense_params(m) + 8 * 40 / 320 * family.expert_params(m)
    assert token == 2.0 * (3 * family.linear_params(m) + family.attention_params(m) + 4 * per_layer) + \
        4.0 * 3 * 64 * 128 * 128 + 4.0 * 64 * 128
    assert family.request_flops(m, 3, 2) == sum(family.token_flops(m, p, p == 2) for p in range(3)) + family.token_flops(m, 3, True)


def test_a_program_without_linear_attention_is_refused_at_once(monkeypatch):
    from k8s_device_plugin_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class OlderConfig:
        vocab_size: int = 0
        mla: None = None
        moe: None = None

    monkeypatch.setattr(transformer, "GPTConfig", OlderConfig)
    m = real()
    with pytest.raises(SystemExit, match="no linear attention"):
        families.load("solar_open2").build(m, m["engine"])
