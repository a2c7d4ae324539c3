"""The family ``longcat_flash`` (chipbench/families/, reference/) through the
harness at a toy size on the CPU: the tiny cell (``data/tiny-longcat-flash.json``
under ``data/tiny-shortchat.json``) is ADDED to a temporary copy as files and
appended entries, rehearses to a valid line with ``correct`` true and every
metric of the cell, reads ``gap_max`` over the limit under the int8 control
and with the expert layer or the second attention dropped from the
reference, and the five metrics this configuration brought read their
numbers without importing JAX."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_helpers as helpers
from chipbench import cells, check_line, families

CONF, MIX = "tiny-longcat-flash", "tiny-shortchat"
CELL = f"{CONF}.{MIX}"
REAL = "longcat-flash-d4e16.agentchat"
NEW_METRICS = ("moe_dropped_assignments", "moe_identity_share", "moe_peak_over_mean_load",
               "expert_share_of_decode_bytes", "latent_share_of_decode_bytes")
# The same replica judged by a reference that leaves a part out.
WITHOUT = '''"""longcat_flash's replica, judged by its reference without {part}."""

from chipbench import families
from chipbench.reference import longcat_flash as ref

_whole = families.load("longcat_flash")
build, params_tree, request_flops, decode_step = _whole.build, _whole.params_tree, _whole.request_flops, _whole.decode_step


def served_gaps(conf, seed, cases, pad_to, control):
    return ref.served_gaps(conf, seed, cases, pad_to, control=control, drop="{part}")
'''
DROPS = ("experts", "attention_1")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = helpers.make_copy(str(tmp_path_factory.mktemp("longcat_flash")))
    here = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(helpers.DATA, f"{CONF}.json")) as f:
        conf = json.load(f)
    shutil.copy(os.path.join(helpers.DATA, f"{MIX}.json"), os.path.join(here, "traffic"))
    variants = [(CONF, conf)]
    for part in DROPS:
        with open(os.path.join(here, "families", f"longcat_flash_no_{part}.py"), "w") as f:
            f.write(WITHOUT.format(part=part))
        variants.append((f"{CONF}-no-{part}", dict(conf, family=f"longcat_flash_no_{part}")))
    for name, body in variants:
        with open(os.path.join(here, "configs", f"{name}.json"), "w") as f:
            json.dump(body, f, indent=1)
        with open(os.path.join(here, "workloads", f"{name}.{MIX}.json"), "w") as f:
            json.dump({"config": name, "traffic": MIX}, f)
        bench["configs"].append({"name": name, "source": "tests", "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "CPU rehearsal of longcat_flash"})
        bench["workloads"].append({"name": f"{name}.{MIX}", "config": name, "traffic": MIX, "chips": 1,
                                   "why": "CPU rehearsal of longcat_flash"})
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
    assert set(NEW_METRICS) < set(units) and len(units) == 13
    assert check_line.problems(text, units, 1, True, "cpu") == []
    assert set(line["metrics"]) == set(units), sorted(set(units) - set(line["metrics"]))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line["compared"]
    got = {name: line["metrics"][name]["value"] for name in NEW_METRICS}
    assert got["moe_dropped_assignments"] == 0
    # 4 zero-computation experts of 12, a seeded router: near a third.
    assert 15 < got["moe_identity_share"] < 55 and got["moe_peak_over_mean_load"] >= 1
    assert 0 < got["expert_share_of_decode_bytes"] < 100 and 0 < got["latent_share_of_decode_bytes"] < 100
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
def scrape(total, identity, held, touched, steps, **more):
    return {"tpu_engine_moe_assignments_total": total, "tpu_engine_moe_identity_assignments_total": identity,
            "tpu_engine_moe_expert_tokens_total": held, "tpu_engine_moe_decode_experts_touched_total": touched,
            "tpu_engine_moe_decode_layer_steps_total": steps, "tpu_engine_moe_dropped_assignments_total": 0.0, **more}
ctx = {"cell": cell, "window": (100.0, 2.0), "results": results, "slots": 4, "capture_interval": (100.0, 101.0),
       "device": {"kind": "TPU v5 lite", "count": 1},
       "scraped": {"before": scrape(600.0, 200.0, 150.0, 40.0, 20.0),
                   "after": scrape(1800.0, 560.0, 450.0, 100.0, 50.0, tpu_engine_moe_expert_tokens_peak=120.0,
                                   tpu_engine_cache_bytes_per_token=192.0)}}
out = {name: cells.load_reader(name)(ctx) for name in sys.argv[2:]}
older = dict(ctx, scraped={"before": {}, "after": {"tpu_engine_requests_total": 17.0}})
out["older"] = [cells.load_reader(name)(older) for name in sys.argv[2:]]
assert "jax" not in sys.modules, "a reader runs in the parent, which never imports JAX"
print(json.dumps(out))
"""


def tiny():
    with open(os.path.join(helpers.DATA, f"{CONF}.json")) as f:
        return json.load(f)


def test_the_five_readers_read_their_numbers_without_jax(copy):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([copy, helpers.REPO]))
    proc = subprocess.run([sys.executable, "-c", IN_THE_COPY, CELL, *NEW_METRICS],
                          cwd=copy, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout)
    assert got["moe_dropped_assignments"] == 0.0
    assert got["moe_identity_share"] == pytest.approx(100.0 * 360 / 1200)
    # 450 tokens over 2 expert layers x 3 held experts: a mean of 75.
    assert got["moe_peak_over_mean_load"] == pytest.approx(120.0 / 75.0)
    # Two of the three requests stream at the capture's middle (100.5; the
    # first ended at 100.1); a step touched 60 / 30 = 2 held experts a layer.
    family, m = families.load("longcat_flash"), tiny()
    contexts = [20 + 3 * i + sum(1 for k in range(12 + i) if 99.0 + 0.4 * i + 0.1 * k <= 100.5) for i in (1, 2)]
    scraped = {"scraped": {"before": {"tpu_engine_moe_decode_experts_touched_total": 40.0, "tpu_engine_moe_decode_layer_steps_total": 20.0},
                           "after": {"tpu_engine_moe_decode_experts_touched_total": 100.0, "tpu_engine_moe_decode_layer_steps_total": 50.0,
                                     "tpu_engine_cache_bytes_per_token": 192.0}}}
    total = family.decode_step(m, contexts, scraped)[1]
    assert family.touched_experts(m, contexts, scraped) == 2.0
    assert family.decode_expert_bytes(m, contexts, scraped) == 2 * 2 * 2.0 * 3 * 64 * 32
    assert family.decode_latent_bytes(m, contexts, scraped) == 192.0 * sum(c + 1 for c in contexts)
    assert family.decode_latent_bytes(m, contexts, {}) == 2 * 4 * (16 + 8) * sum(c + 1 for c in contexts)  # from the shapes
    assert got["expert_share_of_decode_bytes"] == pytest.approx(100.0 * family.decode_expert_bytes(m, contexts, scraped) / total)
    assert got["latent_share_of_decode_bytes"] == pytest.approx(100.0 * family.decode_latent_bytes(m, contexts, scraped) / total)
    # A program without the counters or the gauge: nothing to read, nothing raised.
    assert got["older"] == [None] * 5


def real():
    with open(os.path.join(helpers.REPO, "chipbench", "configs", "longcat-flash-d4e16.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths_and_says_its_cuts():
    m = real()
    cuts = ["num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert m["family"] == "longcat_flash" and m["reduced"] == cuts
    assert set(cuts) == set(m["published"]) == set(m["why_reduced"])
    assert m["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072, "max_position_embeddings": 131072}
    assert [m[k] for k in cuts] == [4, 16, 16384, 1536]
    assert m["max_position_embeddings"] == m["engine"]["page_size"] * m["engine"]["max_pages_per_seq"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    assert m["source"] == row["source_url"]
    assert {k: m[k] for k in row["config"] if k not in cuts} == {k: v for k, v in row["config"].items() if k not in cuts}
    dep = m["deployment"]
    assert dep["chips_sharing_a_layer"] * m["n_routed_experts"] == m["published"]["n_routed_experts"]
    assert dep["held_experts"] == list(range(16)) and dep["vocabulary_slices"] * m["vocab_size"] == 131072
    assert dep["pipeline_stages"] * dep["layers_per_stage"] == 28
    for key in ("torch_dtype", "mla_scale_q_lora", "mla_scale_kv_lora", "norm_topk_prob", "router_bias",
                "e_score_correction_bias", "tie_word_embeddings", "weights", "cache_row", "slots", "num_pages"):
        assert key in m["assumed"]
    with open(os.path.join(helpers.REPO, "chipbench", "traffic", "agentchat.json")) as f:
        assert json.load(f)["clients"] == m["engine"]["slots"] == 64  # never more clients than slots


def test_the_counts_of_work_are_the_issues_reckoning():
    m, family = real(), families.load("longcat_flash")
    assert family.attention_params(m) == 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
    assert family.layer_dense_params(m) == pytest.approx(639e6, rel=0.005)
    assert family.expert_params(m) == 3 * 6144 * 2048
    weights = 4 * (family.layer_dense_params(m) + 16 * family.expert_params(m)) + 2 * 16384 * 6144
    assert 2 * weights == pytest.approx(10.35e9, rel=0.005)  # the tree's bytes, embedding included
    assert family.cache_bytes_per_token(m, {}) == 9216
    contexts = [900] * 64
    flops, nbytes = family.decode_step(m, contexts, {})
    # Without the run's counters, uniform routing's expectation: about 10 of 16 held experts a layer.
    assert family.touched_experts(m, contexts, {}) == pytest.approx(16 * (1 - (1 - 12 / 768) ** 64)) and 10 < family.touched_experts(m, contexts, {}) < 10.4
    experts, latent = family.decode_expert_bytes(m, contexts, {}), family.decode_latent_bytes(m, contexts, {})
    assert experts == pytest.approx(3.07e9, rel=0.01) and latent == 9216 * 64 * 901
    assert nbytes == family.dense_weight_bytes(m) + experts + latent
    assert nbytes / 819e9 == pytest.approx(10.9e-3, rel=0.03)  # the least time of a step
    # The counters, where the run scraped them, are what the experts' bytes are counted from.
    ctx = {"scraped": {"before": {}, "after": {"tpu_engine_moe_decode_experts_touched_total": 800.0,
                                               "tpu_engine_moe_decode_layer_steps_total": 100.0}}}
    assert family.decode_expert_bytes(m, contexts, ctx) == 2.0 * 4 * 8.0 * family.expert_params(m)
    token = family.token_flops(m, 0, False)
    assert token == 2.0 * 4 * (family.layer_dense_params(m) + 0.25 * family.expert_params(m)) + 2.0 * 4 * 2 * 64 * 320
    assert token == pytest.approx(5.1e9, rel=0.02)
    assert family.request_flops(m, 3, 2) == sum(family.token_flops(m, p, p == 2) for p in range(3)) + family.token_flops(m, 3, True)


def test_a_program_without_latent_attention_is_refused_at_once(monkeypatch):
    from k8s_device_plugin_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class OlderConfig:
        vocab_size: int = 0
        mixer: None = None

    monkeypatch.setattr(transformer, "GPTConfig", OlderConfig)
    m = real()
    with pytest.raises(SystemExit, match="no latent attention"):
        families.load("longcat_flash").build(m, m["engine"])
