"""A temporary copy of the benchmark with tiny cells and a dummy metric
ADDED to it, the way a later PR adds them: new files and appended entries,
no file that exists edited."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

DUMMY_READER = '''"""A dummy per-layer metric: requests the window sent."""


def read(ctx):
    return len(ctx["results"])
'''


def make_copy(tmp: str, train: bool = False) -> str:
    """``tmp/BENCHMARK.json`` and ``tmp/chipbench`` with the tiny cells."""
    shutil.copytree(os.path.join(REPO, "chipbench"), os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(tmp, "chipbench")
    shutil.copy(os.path.join(DATA, "tiny-llm.json"), os.path.join(here, "configs"))
    bench["configs"].append({"name": "tiny-llm", "source": "tests", "file": "chipbench/configs/tiny-llm.json",
                             "reduced": [], "why": "CPU rehearsal"})
    cells = {"tiny-llm.tiny-chat": ("tiny-llm", "tiny-chat"), "tiny-llm.tiny-batch": ("tiny-llm", "tiny-batch")}
    if train:
        shutil.copy(os.path.join(DATA, "tiny-resnet.json"), os.path.join(here, "configs"))
        bench["configs"].append({"name": "tiny-resnet", "source": "tests", "file": "chipbench/configs/tiny-resnet.json",
                                 "reduced": [], "why": "CPU rehearsal"})
        cells["tiny-resnet.train"] = ("tiny-resnet", "train")
    for cell, (conf, mix) in cells.items():
        if mix != "train":
            shutil.copy(os.path.join(DATA, f"{mix}.json"), os.path.join(here, "traffic"))
        with open(os.path.join(here, "workloads", f"{cell}.json"), "w") as f:
            json.dump({"config": conf, "traffic": mix}, f)
        bench["workloads"].append({"name": cell, "config": conf, "traffic": mix, "chips": 1, "why": "CPU rehearsal"})
    like = {"tiny-llm.tiny-chat": "mistral7b-d16.chat", "tiny-llm.tiny-batch": "mistral7b-d16.batch",
            "tiny-resnet.train": "resnet50-b128.train"}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [c for c in cells if like[c] in metric["workloads"]]
    with open(os.path.join(here, "readers", "dummy_count.py"), "w") as f:
        f.write(DUMMY_READER)
    dummy = {"name": "dummy_count", "unit": "count", "better": "higher", "source": "program_counter",
             "layer": "load generator (chipbench/loadgen.py)", "moves": "itl_p95_ms",
             "workloads": ["tiny-llm.tiny-chat"]}
    with open(os.path.join(here, "metrics", "dummy_count.json"), "w") as f:
        json.dump(dummy, f)
    bench["per_layer"].append(dummy)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return tmp


def run_cell(root: str, cell: str, *extra: str, seed: int = 7, seconds: float = 3.0, trace: int = 0, timeout: float = 600):
    """``python -m chipbench.run`` in the copy; the program comes from the repo."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([root, REPO]), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    env.pop("XLA_FLAGS", None)  # tests/conftest.py asks for 8 host devices; a cell has 1
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )
