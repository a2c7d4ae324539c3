"""Per-chip-generation kernel tuning tables for the decode kernels.

The split-K paged-attention kernel (ops/paged_attention.py) has one
load-bearing free parameter — how many grid programs share one
sequence's page list — and the right answer is a property of the CHIP
(how many sequential page fetches amortize one program's setup, how
much VMEM a partial-state triple costs), not of the model.  This module
owns that knowledge the same way ops/flash_attention.py owns its block
tables: small reviewed rows keyed by TPU generation, matched against
what the plugin actually discovered.

Grounding (the MT4G pattern, PAPERS.md): the serving container never
guesses its chip.  The plugin daemon discovers the accelerator type at
registration (plugin/discovery.py) and Allocate injects it as
``TPU_ACCELERATOR_TYPE`` alongside ``TPU_CHIPS_PER_HOST_BOUNDS``
(plugin/envs.py), so the engine's tuning lookup keys off the SAME
topology source the mesh derivation uses (parallel/mesh.py) — with
``jax.devices()[0].device_kind`` as the on-chip tie-breaker and an
interpret-mode-safe default row for CPU smoke.

Row schema (see docs/kernels.md "Tile-table schema" for how a hardware
round records a new row):

- ``generation``  — device_kind prefix the row matches (or "cpu");
- ``min_pages_per_split`` — never split below this many pages per
  program: each split re-pays the online-softmax state init and one
  combine term, so thin splits trade HBM streaming for overhead;
- ``max_splits`` — cap on the split axis (bounds the partial buffers
  and the combine's reduction width);
- ``source`` — provenance: which bench round measured it, or why the
  row is provisional.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Mapping, Optional


@dataclass(frozen=True)
class DecodeRow:
    """One generation's split-K decode tuning row."""

    generation: str
    min_pages_per_split: int
    max_splits: int
    source: str


# Keyed by device_kind prefix (the flash-attention table's convention;
# the v5e reports "TPU v5 lite").  The split counts are still unswept:
# PR 21's chip run proved that the v5 lite row's splits (8 at the shipped
# 32-page rows) lower under Mosaic and agree with the gather path for
# float, int8 and int4 pools, and timed nothing — `use_kernel` stays
# opt-in until a cell measures kernel against gather
# (models/transformer.py PagedConfig, ROADMAP Speed 5).
DECODE_ROWS: tuple[DecodeRow, ...] = (
    DecodeRow("TPU v5 lite", 4, 8, "lowered and parity-checked on v5e (PR 21); speed not measured"),
    DecodeRow("TPU v5e", 4, 8, "alias of the v5 lite row"),
    DecodeRow("TPU v5p", 4, 8, "provisional: inherits v5e, never run"),
    DecodeRow("TPU v4", 4, 4, "provisional: smaller VMEM, fewer splits, never run"),
    DecodeRow("TPU v6", 4, 8, "provisional: inherits v5e, never run"),
)

# CPU smoke / Pallas interpreter: splitting buys nothing (no DMA
# pipeline to parallelize) and every extra split is pure combine
# overhead, so the safe row is the degenerate 1-split — which is also
# what keeps the KERNELS ledger's CPU rows honest about the kernel's
# structure rather than its split bookkeeping.
CPU_ROW = DecodeRow("cpu", 1 << 30, 1, "interpret-mode-safe default")

# Unknown TPU generation: conservative splits so the kernel stays
# usable while the missing row is the visible gap (the engine meters it
# as a kernel.fallback, reason=untuned_generation).
FALLBACK_ROW = DecodeRow("unknown-tpu", 8, 2, "no row for this generation")

# TPU_ACCELERATOR_TYPE prefixes (plugin/discovery.py values like
# "v5litepod-8") -> the device_kind prefix the rows key on.
_ACCEL_TYPE_PREFIXES: tuple[tuple[str, str], ...] = (
    ("v5litepod", "TPU v5 lite"),
    ("v5e", "TPU v5e"),
    ("v5p", "TPU v5p"),
    ("v4", "TPU v4"),
    ("v6", "TPU v6"),
)


def device_generation(environ: Optional[Mapping[str, str]] = None) -> str:
    """The generation key tuning rows match against.

    The live backend's device_kind when jax sits on a TPU (a backend
    that fails to initialise raises here — it is never read as "cpu");
    on any other backend the plugin-injected ``TPU_ACCELERATOR_TYPE``
    (present in every Allocate-launched serving container), else "cpu".
    """
    import jax

    if jax.default_backend() == "tpu":
        return jax.devices()[0].device_kind
    env = os.environ if environ is None else environ
    accel = env.get("TPU_ACCELERATOR_TYPE", "")
    for prefix, kind in _ACCEL_TYPE_PREFIXES:
        if accel.startswith(prefix):
            return kind
    return "cpu"


def _row_for(generation: Optional[str], rows, cpu_row, fallback_row):
    """``(row, exact)`` of a table keyed by device_kind prefix: the CPU
    row off the chip, the fallback row for a TPU generation without one."""
    kind = device_generation() if generation is None else generation
    if kind == "cpu":
        return cpu_row, True
    for row in rows:
        if kind.startswith(row.generation):
            return row, True
    return fallback_row, False


def decode_row(generation: Optional[str] = None) -> tuple[DecodeRow, bool]:
    """The tuning row for ``generation`` (default: discovered) and
    whether it was an exact match (False = the conservative fallback —
    the engine's untuned-generation fallback signal)."""
    return _row_for(generation, DECODE_ROWS, CPU_ROW, FALLBACK_ROW)


def has_row(generation: Optional[str] = None) -> bool:
    """Whether a reviewed tuning row exists for this generation."""
    return decode_row(generation)[1]


@dataclass(frozen=True)
class ExpertFfnRow:
    """One generation's row for the grouped expert FFN (ops/expert_ffn.py):
    the tile of the f axis that one grid step streams (a gate, an up and a
    down block of ``h x tile_f`` elements each, double-buffered by the
    pipeline) and the scoped VMEM the kernel may take."""

    generation: str
    tile_f: int
    vmem_limit_bytes: Optional[int]  # None: the compiler's default
    source: str


# Reckoned at LongCat-Flash's widths (h 6144, f 2048, bf16): a block is
# h x tile_f x 2 bytes, three blocks a step, each double-buffered, beside
# the resident rows and float32 output (64 rows: 0.8 + 1.6 MB, 256 rows:
# 3.1 + 6.3 MB, each double-buffered too).  tile_f 256 is 18.9 MB of
# weight buffers, already over the 16 MiB default scoped limit, so the
# limit is raised; a v5e core has 128 MiB of VMEM.  Timed alone on a v5e
# (chip run, PR 33: one layer, 64 rows, 8 of 16 experts touched, 604 MB):
# tile 128 0.845 ms, 256 0.893, 512 0.842, 1,024 0.848; the XLA lane 0.978.
EXPERT_FFN_ROWS: tuple[ExpertFfnRow, ...] = (
    ExpertFfnRow(
        "TPU v5 lite", 512, 96 << 20,
        "0.842 ms at 64 rows, 8 of 16 touched (717 GB/s); the XLA lane 0.978 (chip run, PR 33)",
    ),
    ExpertFfnRow("TPU v5e", 512, 96 << 20, "alias of the v5 lite row"),
)

# The Pallas interpreter has no VMEM: two tiles of a test's 256-wide f, so
# the sum over tiles and the held-block index map both run.
EXPERT_FFN_CPU_ROW = ExpertFfnRow("cpu", 128, None, "interpret-mode default")

# A TPU generation without a row: the tile that fits the default scoped
# limit of every generation so far, and no raised limit.
EXPERT_FFN_FALLBACK_ROW = ExpertFfnRow("unknown-tpu", 128, None, "no row for this generation")


def expert_ffn_row(generation: Optional[str] = None) -> tuple[ExpertFfnRow, bool]:
    """The grouped expert FFN's row for ``generation`` (default:
    discovered) and whether it was an exact match."""
    return _row_for(generation, EXPERT_FFN_ROWS, EXPERT_FFN_CPU_ROW, EXPERT_FFN_FALLBACK_ROW)


def pick_num_splits(
    pages_per_seq: int, generation: Optional[str] = None
) -> int:
    """Split-K degree for a sequence of ``pages_per_seq`` table entries.

    Largest power-of-two split count that (a) stays within the row's
    ``max_splits`` and (b) leaves every split at least
    ``min_pages_per_split`` pages of real streaming work.  Degenerates
    to 1 for short contexts (the combine stage is skipped entirely
    there — ops/paged_attention.py) and on the CPU row.
    """
    if pages_per_seq < 1:
        raise ValueError(f"pages_per_seq must be >= 1, got {pages_per_seq}")
    row, _ = decode_row(generation)
    splits = 1
    while (
        splits * 2 <= row.max_splits
        and pages_per_seq // (splits * 2) >= row.min_pages_per_split
    ):
        splits *= 2
    return min(splits, pages_per_seq)
