"""A serving configuration's model family: the one place that knows how a
published ``config.json`` becomes this program's model, its seeded weights,
its plain reference and its count of work.  Found by name, as a reader is:
``families/<name>.py`` under the benchmark root the run was started in.

A configuration file names its family under ``"family"``; a file without
the key is of the family ``llm``.  A family offers exactly these names:

``build(model, engine) -> (cfg, paged)``
    what ``ServingEngine`` takes first; everything model-specific reaches
    the engine through ``cfg``, the keyword wiring stays in ``serve_child.py``.
``params_tree(model, seed_words)``
    the served tree in the program's layout, called under one ``jax.jit``
    with the seed as its argument (chipbench/weights.py: no seed closed over).
``served_gaps(conf, seed, cases, pad_to, control)``
    the reference's rows (``gaps``, ``ref_argmax``, with ``control`` also
    ``control_gaps``), from ``reference/<name>.py``, which makes its leaves
    again from the seed and takes nothing the program has made.
``request_flops(model, prompt_tokens, output_tokens)``
``decode_step(model, contexts, ctx) -> (flops, bytes)``
    the work the algorithm needs.  ``ctx`` is the run's (scraped counters,
    results, trace), for a family whose bytes depend on what the run did.

The last two are called in the parent, which never imports JAX: a family
imports JAX, ``weights.py`` and its reference inside the first three only.
"""

from __future__ import annotations

import importlib.util
import os

from ..cells import BENCH_ROOT
from ..check_line import NAME

OFFERS = ("build", "params_tree", "served_gaps", "request_flops", "decode_step")


def load(name: str, bench_root: str = BENCH_ROOT):
    """The module ``chipbench/families/<name>.py`` of ``bench_root``."""
    if not NAME.match(name):
        raise ValueError(f"family {name!r} is no name (check_line.NAME)")
    path = os.path.join(bench_root, "chipbench", "families", f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no family {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(f"chipbench_family_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [n for n in OFFERS if not callable(getattr(module, n, None))]
    if missing:
        raise ValueError(f"family {name!r} ({path}) lacks {missing}")
    return module


def of(conf: dict):
    """The family of a configuration (its file's keys)."""
    return load(conf.get("family", "llm"))
