"""A configuration's model family (chipbench/families/): the harness finds
launcher, seeded weights, reference and count of work by the name in the
configuration's file.

The seam: a second family (``data/toy_mixed/``) is ADDED to a temporary copy
as files and appended entries alone, runs a rehearsal cell to a valid line
with ``correct`` true, and is judged not correct by ``llm``'s reference.
Nothing moved: ``families/llm.py`` is ``weights.py``, ``reference/llm.py``
and ``flops.py`` under the five names, bit for bit.
"""

import filecmp
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

import chipbench_helpers as helpers
from chipbench import cells, check_line, families, flops, trace

TOY = os.path.join(helpers.DATA, "toy_mixed")
CELL = "tiny-mixed.tiny-batch"
# The toy's weights under ``llm``'s reference: a third family, files alone.
# (Striking ``"family"`` from the file would hand the replica ``llm``'s
# weights too, and ``llm`` judges its own weights correct.)
JUDGED_BY_LLM = '''"""toy_mixed's replica, judged by llm's reference."""

from chipbench import families

_toy = families.load("toy_mixed")
build, params_tree, request_flops, decode_step = _toy.build, _toy.params_tree, _toy.request_flops, _toy.decode_step
served_gaps = families.load("llm").served_gaps
'''


def files_under(root):
    for base, dirs, names in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            yield os.path.relpath(os.path.join(base, name), root)


def add_cell(root, bench, cell, conf_name, conf):
    """One more configuration and its batch cell, appended."""
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "configs", f"{conf_name}.json"), "w") as f:
        json.dump(conf, f, indent=1)
    with open(os.path.join(here, "workloads", f"{cell}.json"), "w") as f:
        json.dump({"config": conf_name, "traffic": "tiny-batch"}, f)
    bench["configs"].append({"name": conf_name, "source": "tests", "file": f"chipbench/configs/{conf_name}.json",
                             "reduced": [], "why": "CPU rehearsal of a second family"})
    bench["workloads"].append({"name": cell, "config": conf_name, "traffic": "tiny-batch", "chips": 1,
                               "why": "CPU rehearsal of a second family"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "mistral7b-d16.batch" in metric.get("workloads", []):
            metric["workloads"].append(cell)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``make_copy``'s copy, then the second family as new files (copied
    from ``data/toy_mixed/``) and entries appended to ``BENCHMARK.json``."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("families")))
    before = set(files_under(os.path.join(root, "chipbench")))
    here = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for sub in ("families", "reference"):
        shutil.copy(os.path.join(TOY, sub, "toy_mixed.py"), os.path.join(here, sub))
    with open(os.path.join(TOY, "configs", "tiny-mixed.json")) as f:
        conf = json.load(f)
    with open(os.path.join(TOY, "workloads", f"{CELL}.json")) as f:
        assert json.load(f) == {"config": "tiny-mixed", "traffic": "tiny-batch"}
    add_cell(root, bench, CELL, "tiny-mixed", conf)
    with open(os.path.join(here, "families", "toy_mixed_by_llm.py"), "w") as f:
        f.write(JUDGED_BY_LLM)
    add_cell(root, bench, "tiny-mixed-by-llm.tiny-batch", "tiny-mixed-by-llm", dict(conf, family="toy_mixed_by_llm"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return NS(root=root, before=before)


def test_second_family_is_files_and_appended_entries_alone(copy):
    """Every file the copy had before the family came is the repo's, byte
    for byte; ``BENCHMARK.json`` kept every entry it had, in its place."""
    repo = set(files_under(os.path.join(helpers.REPO, "chipbench")))
    assert repo <= copy.before
    now = set(files_under(os.path.join(copy.root, "chipbench")))
    assert {"families/toy_mixed.py", "reference/toy_mixed.py", "configs/tiny-mixed.json",
            f"workloads/{CELL}.json"} <= now - copy.before
    same, differ, errors = filecmp.cmpfiles(os.path.join(helpers.REPO, "chipbench"),
                                            os.path.join(copy.root, "chipbench"), sorted(repo), shallow=False)
    assert (differ, errors) == ([], []) and len(same) == len(repo)
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        had = json.load(f)
    with open(os.path.join(copy.root, "BENCHMARK.json")) as f:
        has = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(had[key], has[key]):
            assert {k: v for k, v in new.items() if k != "workloads"} == {k: v for k, v in old.items() if k != "workloads"}
            assert new.get("workloads", [])[: len(old.get("workloads", []))] == old.get("workloads", [])


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert len(lines) == 1
    return lines[0], json.loads(lines[0])


@pytest.mark.parametrize("cell,trace,correct", [
    (CELL, 0, True),
    (CELL, 1, True),
    ("tiny-mixed-by-llm.tiny-batch", 0, False),
])
def test_second_family_rehearses_and_llms_reference_does_not_agree(copy, cell, trace, correct):
    text, line = last_line(helpers.run_cell(copy.root, cell, "--rehearse", "cpu", trace=trace))
    units = cells.load_cell(cell, copy.root).units(bool(trace))
    assert check_line.problems(text, units, 1, bool(trace), "cpu") == []
    assert set(line["metrics"]) == set(units) and line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"] is correct, line["compared"]
    gap = line["compared"]["gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct and line["compared"]["wrong_length"]["value"] == 0
    if not correct:
        assert gap["value"] > 4 * gap["limit"]  # other weights altogether, not rounding


def test_control_of_the_second_family_comes_from_its_own_reference(copy):
    proc = helpers.run_cell(copy.root, CELL, "--rehearse", "cpu", "--control", "1", seed=11)
    assert proc.returncode in (0, 4), proc.stderr[-3000:]
    line = json.loads(proc.stdout.splitlines()[-1])
    assert line["correct"] is True and set(line["controls"]) == {"control_int8"}
    assert line["controls"]["control_int8"]["gap_max"]["limit"] == line["compared"]["gap_max"]["limit"]


IN_THE_COPY = r"""
import json, sys
from types import SimpleNamespace as NS
from chipbench import cells
from chipbench.readers import _traced
assert "jax" not in sys.modules
results = [NS(prompt_tokens=20 + 3 * i, token_times=[99.0 + 0.4 * i + 0.1 * k for k in range(6 + i)]) for i in range(5)]
out = {}
for cell in sys.argv[1:]:
    ctx = {"cell": cells.load_cell(cell), "window": (100.0, 2.0), "results": results,
           "device": {"kind": "TPU v5 lite", "count": 1}, "scraped": {"after": {"toy_mixed_extra_bytes": 819e9}}}
    out[cell] = {"serve.mfu": cells.load_reader("serve.mfu")(ctx), "least": _traced.step_least_s(ctx, [30, 40])}
assert "jax" not in sys.modules, "a reader runs in the parent, which never imports JAX"
print(json.dumps(out))
"""


def test_readers_count_work_as_the_cells_family_does(copy):
    """``serve.mfu`` reads the toy's own ``request_flops`` and
    ``step_least_s`` its ``decode_step``, handed the run's ``ctx``; the
    ``llm`` cell beside it reads ``flops.py``; neither imports JAX."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([copy.root, helpers.REPO]))
    proc = subprocess.run([sys.executable, "-c", IN_THE_COPY, CELL, "tiny-llm.tiny-batch"],
                          cwd=copy.root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout)
    spec = importlib.util.spec_from_file_location("toy_mixed_family", os.path.join(TOY, "families", "toy_mixed.py"))
    toy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(toy)
    with open(os.path.join(helpers.DATA, "tiny-llm.json")) as f:
        m = json.load(f)

    def mfu(request_flops):
        # By hand: window 100..102; request i streams 6 + i tokens from 99 + 0.4 i, 0.1 s apart.
        total = 0.0
        for i in range(5):
            times = [99.0 + 0.4 * i + 0.1 * k for k in range(6 + i)]
            inside = [100.0 <= t < 102.0 for t in times]
            prefill = request_flops(m, 20 + 3 * i, 1)
            decode = request_flops(m, 20 + 3 * i, len(times)) - prefill
            total += (prefill if inside[0] else 0.0) + decode * (sum(inside) - inside[0]) / (len(times) - 1)
        return 100.0 * total / (2.0 * 197e12)

    assert got[CELL]["serve.mfu"] == pytest.approx(mfu(toy.request_flops), rel=1e-12)
    assert got["tiny-llm.tiny-batch"]["serve.mfu"] == pytest.approx(mfu(flops.llm_request_flops), rel=1e-12)
    assert got[CELL]["serve.mfu"] != pytest.approx(got["tiny-llm.tiny-batch"]["serve.mfu"], rel=0.05)
    f, b = flops.llm_decode_step(m, [30, 40])
    assert got["tiny-llm.tiny-batch"]["least"] == [pytest.approx(max(f / 197e12, b / 819e9)), "bytes"]
    assert got[CELL]["least"] == [pytest.approx((b + 819e9) / 819e9), "bytes"]  # the counter in ``ctx`` reached it


# ------------------------------------------------------- nothing moved ----


def tiny():
    with open(os.path.join(helpers.DATA, "tiny-llm.json")) as f:
        return json.load(f)


def test_absent_key_means_llm_and_a_family_offers_the_five_names(tmp_path):
    assert "family" not in tiny()
    assert families.of(tiny()).__file__ == os.path.join(helpers.REPO, "chipbench", "families", "llm.py")
    with open(os.path.join(helpers.REPO, "chipbench", "configs", "mistral7b-d16.json")) as f:
        assert "family" not in json.load(f)
    assert families.OFFERS == ("build", "params_tree", "served_gaps", "request_flops", "decode_step")
    os.makedirs(tmp_path / "chipbench" / "families")
    (tmp_path / "chipbench" / "families" / "short.py").write_text("def build(model, engine):\n    pass\n")
    with pytest.raises(ValueError, match="lacks"):
        families.load("short", str(tmp_path))
    with pytest.raises(ValueError, match="is not there"):
        families.load("absent", str(tmp_path))
    with pytest.raises(ValueError, match="is no name"):
        families.load("../llm")


def test_llm_family_builds_what_the_launcher_built():
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.transformer import GPTConfig, PagedConfig

    m = tiny()
    cfg, paged = families.load("llm").build(m, m["engine"])
    assert cfg == GPTConfig(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=128,
                            max_seq=128, rope_theta=10000.0, num_kv_heads=2, attention_window=48, dtype=jnp.bfloat16)
    assert paged == PagedConfig(16, 96, 8)


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_llm_family_makes_the_same_tree_bit_for_bit(seed):
    import jax

    from chipbench import weights

    m, words = tiny(), weights.seed_words(seed)
    family = families.load("llm")
    got = jax.jit(lambda w: family.params_tree(m, w))(words)
    want = jax.jit(lambda w: weights.llm_params_tree(m, w))(words)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))


def test_llm_family_reference_rows_are_reference_llms():
    from chipbench.reference import llm

    m = tiny()
    rng = np.random.default_rng(5)
    cases = [{"prompt": rng.integers(0, 512, size=n).tolist(), "tokens": rng.integers(0, 512, size=k).tolist()}
             for n, k in ((9, 5), (17, 8))]
    got = families.load("llm").served_gaps(m, 13, cases, 32, True)
    assert got == llm.served_gaps(m, 13, cases, 32, control=True)
    assert set(got[0]) == {"gaps", "ref_argmax", "control_gaps"} and len(got[1]["gaps"]) == 8


def recorded_ctx():
    """The batch cell over the trace recorded on the v5e (``data/tpu1.xplane.pb``:
    three rounds of a 4-step ``jit_block`` and a ``jit_step``) and a made-up
    client's record; the numbers pinned below are the parent commit's."""
    t0 = 1000.0
    results = [NS(prompt_tokens=128 + 25 * i, token_times=[t0 - 2.0 + 0.9 * i + 0.05 * k for k in range(40 + 9 * i)])
               for i in range(12)]
    return {"cell": cells.load_cell("mistral7b-d16.batch"), "window": (t0, 8.0), "results": results,
            "device": {"kind": "TPU v5 lite", "count": 1},
            "trace_reduced": trace.reduce(trace.load(os.path.join(helpers.DATA, "tpu1.xplane.pb"))),
            "capture_interval": (t0 + 3.0, t0 + 6.0)}


@pytest.mark.parametrize("metric,pinned", [
    ("serve.mfu", 1.4853748372142133),
    # Mistral's bytes over a toy program's device time: no share of anything, the arithmetic alone.
    ("decode_block_roofline", 1290752.6913572932),
])
def test_readers_read_what_they_read_before_the_family(metric, pinned):
    assert cells.load_reader(metric)(recorded_ctx()) == pytest.approx(pinned, rel=1e-12)


def test_step_least_s_is_flops_llm_decode_step():
    from chipbench.readers import _traced

    ctx = recorded_ctx()
    contexts = _traced.live_contexts(ctx)
    assert contexts == [287, 294, 301, 308]
    assert _traced.step_least_s(ctx, contexts) == (pytest.approx(0.008938032136752136, rel=1e-12), "bytes")
    f, b = flops.llm_decode_step(ctx["cell"].config["model"], contexts)
    assert families.load("llm").decode_step(ctx["cell"].config["model"], contexts, ctx) == (f, b)
