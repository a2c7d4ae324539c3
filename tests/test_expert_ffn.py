"""The grouped expert FFN (ops/expert_ffn.py) on its two lanes: the kernel
through the Pallas interpreter against the XLA lane (the ``fori_loop`` of
``skip`` / ``full`` that serves off the TPU), inside ``models/moe.py``'s
expert layer at a toy size in bfloat16; then the kernel compiled for a
described v5e at LongCat-Flash's widths, which the interpreter cannot
refuse (tiling, VMEM), and the decode programs of a latent paged cache
compiled for it, whose layouts only the chip's compiler chooses; the
latent decode kernel (ops/latent_attention.py) too, alone at the same
widths and inside those decode programs.  Every compile for the described
chip lives in this file: one worker loads the TPU's library for all of
them."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families
from chipbench import weights
from k8s_device_plugin_tpu.models import mla, moe
from k8s_device_plugin_tpu.models.engine_sampling import build_block_fn, build_step_fn
from k8s_device_plugin_tpu.models.transformer import GPTConfig, TransformerLM, decode_cache_spec
from k8s_device_plugin_tpu.ops import expert_ffn as kernel
from k8s_device_plugin_tpu.ops import kda_decode, latent_attention
from k8s_device_plugin_tpu.ops import tuning

with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-longcat-flash.json")) as f:
    MODEL = json.load(f)
HIDDEN, EXPERT = 128, 256  # two tiles of the CPU row's 128 along f
ROUTINGS = ("seeded", "no expert", "one token on one expert", "every expert by every token",
            "all tokens on one expert", "masked rows")


def layer_config(held):
    cfg, _ = families.load("longcat_flash").build(MODEL, {"page_size": 4, "num_pages": 8, "max_pages_per_seq": 4})
    return dataclasses.replace(
        cfg, hidden_size=HIDDEN, dtype=jnp.bfloat16, moe=dataclasses.replace(cfg.moe, expert_size=EXPERT, held=held)
    )


def routed(cfg, routing, rows):
    """Parameters, hidden rows and a token mask that make ``routing``
    happen: the selection bias decides the choice (top-3 of 8 + 4)."""
    held = list(cfg.moe.held)
    u = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, HIDDEN), jnp.float32).astype(jnp.bfloat16)
    params = moe.ExpertLayer(cfg).init(jax.random.PRNGKey(7), u)["params"]
    bias, mask = np.zeros((cfg.moe.width,), np.float32), np.ones((1, rows), bool)
    if routing == "no expert":
        bias[held] = -10.0
    elif routing == "one token on one expert":
        bias[held], bias[held[1]] = -10.0, 10.0
        mask[:] = False
        mask[0, rows // 3] = True
    elif routing == "every expert by every token":
        bias[held] = 10.0
    elif routing == "all tokens on one expert":
        bias[held[2]] = 10.0
    elif routing == "masked rows":
        mask[0, rows // 2:] = False
    return {**params, "select_bias": jnp.asarray(bias)}, u, jnp.asarray(mask)


def on_lane(cfg, params, u, mask, monkeypatch, lane):
    """The layer's output and sown vector on one lane, and what the lane's
    ``expert_ffn`` was handed."""
    handed = []

    def spy(rows, weight_of, counts, *stacks):
        handed.append(np.asarray(counts))
        how = {"use_pallas": False} if lane == "xla" else {"interpret": True}
        return kernel.expert_ffn(rows, weight_of, counts, *stacks, **how)

    monkeypatch.setattr(moe, "expert_ffn", spy)
    out, mut = moe.ExpertLayer(cfg).apply({"params": params}, u, mask, mutable=["moe_stats"])
    [counts] = handed
    return np.asarray(out[0], np.float32), np.asarray(mut["moe_stats"]["counts"][0]), counts


@pytest.mark.parametrize("held", [(0, 2, 5), (0, 1, 2)], ids=["held 0 2 5", "held 0 1 2"])
@pytest.mark.parametrize("rows", [64, 256])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_kernel_is_the_xla_lane(routing, rows, held, monkeypatch):
    cfg = layer_config(held)
    assert moe.few_tokens(rows)
    params, u, mask = routed(cfg, routing, rows)
    want, want_stats, counts = on_lane(cfg, params, u, mask, monkeypatch, "xla")
    got, got_stats, got_counts = on_lane(cfg, params, u, mask, monkeypatch, "kernel")
    # bfloat16 out: a last-place step of the largest value covers the
    # float32 sum's other order (tile by tile along f).
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2 * max(np.abs(want).max(), 1e-3))
    assert np.array_equal(got_stats, want_stats) and np.array_equal(got_counts, counts)
    stats = dict(zip(moe.STATS, want_stats))
    assert stats["dropped"] == 0 and stats["held"] == counts.sum() and stats["touched"] == (counts > 0).sum()
    # What the routing was meant to be.
    real = int(np.asarray(mask).sum())
    expected = {
        "no expert": lambda: counts.sum() == 0 and stats["active"] == 1,
        "one token on one expert": lambda: counts.tolist() == [0, 1, 0],
        "every expert by every token": lambda: counts.tolist() == [rows] * 3,
        "all tokens on one expert": lambda: counts[2] == rows,
        "masked rows": lambda: real == rows // 2 and counts.max() <= real and np.all(got[rows // 2:] == 0),
        "seeded": lambda: 0 < counts.sum() < 3 * rows,
    }[routing]
    assert expected(), (routing, counts, stats)
    # The blocks the kernel's index maps name: touched experts ascending,
    # then the last one again; an untouched expert's block never.
    order, n_touched = (np.asarray(v) for v in kernel.visit_order(jnp.asarray(counts)))
    touched = np.flatnonzero(counts > 0)
    assert n_touched == len(touched) and order[:n_touched].tolist() == touched.tolist()
    if len(touched):
        assert set(order.tolist()) == set(touched.tolist()) and np.all(order[n_touched:] == touched[-1])
    else:
        assert np.all(order == 0)


def test_computed_is_what_the_kernel_was_handed():
    """``computed`` sums the counts of the experts in ``order[:n_touched]``:
    with a count the weights do not bear out, both lanes still report the
    count, and an expert whose count is 0 is left out though rows weigh on
    it."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    rows = jax.random.normal(ks[0], (32, HIDDEN), jnp.float32).astype(jnp.bfloat16)
    stacks = [
        (jax.random.normal(k, shape, jnp.float32) * shape[1] ** -0.5).astype(jnp.bfloat16)
        for k, shape in zip(ks[1:4], [(4, HIDDEN, EXPERT), (4, HIDDEN, EXPERT), (4, EXPERT, HIDDEN)])
    ]
    weight_of = jax.random.uniform(ks[4], (4, 32))
    counts = jnp.asarray([5, 0, 32, 0], jnp.int32)
    want, want_n = kernel.expert_ffn(rows, weight_of, counts, *stacks, use_pallas=False)
    got, got_n = kernel.expert_ffn(rows, weight_of, counts, *stacks, interpret=True, tile_f=64)
    assert int(want_n) == int(got_n) == 37
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    only = kernel.expert_ffn(rows, weight_of.at[jnp.asarray([1, 3])].set(0.0), counts, *stacks, use_pallas=False)[0]
    np.testing.assert_array_equal(np.asarray(only), np.asarray(want))


def test_rows_off_the_sublane_tile_are_padded_and_cut():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    rows = jax.random.normal(ks[0], (20, HIDDEN), jnp.float32)
    stacks = [jax.random.normal(k, shape, jnp.float32) * 0.1
              for k, shape in zip(ks[1:4], [(2, HIDDEN, EXPERT), (2, HIDDEN, EXPERT), (2, EXPERT, HIDDEN)])]
    weight_of = jnp.where(jax.random.uniform(ks[4], (2, 20)) < 0.4, 1.5, 0.0)
    counts = jnp.sum(weight_of != 0, axis=1, dtype=jnp.int32)
    want, _ = kernel.expert_ffn(rows, weight_of, counts, *stacks, use_pallas=False)
    got, _ = kernel.expert_ffn(rows, weight_of, counts, *stacks, interpret=True)
    assert got.shape == (20, HIDDEN)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_shapes_that_do_not_belong_together_are_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="weight_of"):
        kernel.expert_ffn(z((8, 16)), z((2, 4)), z((2,), jnp.int32), z((2, 16, 32)), z((2, 16, 32)), z((2, 32, 16)))
    with pytest.raises(ValueError, match="stacks"):
        kernel.expert_ffn(z((8, 16)), z((2, 8)), z((2,), jnp.int32), z((2, 16, 32)), z((2, 16, 32)), z((2, 16, 32)))


def test_the_lane_is_the_backends_and_a_row_is_a_generations():
    assert not kernel.on_kernel_lane(), "the tests run on the CPU"
    assert tuning.expert_ffn_row("cpu") == (tuning.EXPERT_FFN_CPU_ROW, True)
    row, exact = tuning.expert_ffn_row("TPU v5 lite")
    assert exact and row.generation == "TPU v5 lite" and 2048 % row.tile_f == 0 and row.source
    # Three double-buffered weight blocks at LongCat-Flash's widths, the
    # rows and the float32 output of a 256-row chunk beside them.
    need = 2 * 3 * 6144 * row.tile_f * 2 + 2 * 256 * 6144 * (2 + 4)
    assert need < row.vmem_limit_bytes <= 128 << 20
    assert tuning.expert_ffn_row("TPU v9")[1] is False


def test_a_model_without_experts_lowers_to_the_same_text(monkeypatch):
    """A configuration without ``moe`` never traces the kernel's module:
    with ``expert_ffn`` made to raise its program is the same text."""
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=32)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), ids))["params"]
    lower = lambda: jax.jit(TransformerLM(cfg).apply).lower({"params": params}, ids).as_text()  # noqa: E731
    before = lower()

    def refuse(*a, **k):
        raise AssertionError("a model without experts reached ops/expert_ffn.py")

    for name in ("expert_ffn", "expert_slice_ffn"):
        monkeypatch.setattr(kernel, name, refuse)
        monkeypatch.setattr(moe, name, refuse)
    assert lower() == before


# ------------------------------------ compiled for the chip, not run ----


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  (whatever keeps libtpu from describing a chip here)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [64, 256])
def test_the_kernel_compiles_for_a_v5e_at_longcat_flash_widths(one_chip, rows):
    """16 held experts of 6144 x 2048 in bfloat16 under the v5 lite row's
    tile and VMEM limit: Mosaic takes it, and no copy of a stack is in the
    compiled program."""
    held, h, f = 16, 6144, 2048
    row, _ = tuning.expert_ffn_row("TPU v5 lite")
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def call(x, weight_of, counts, gate, up, down):
        order, n_touched = kernel.visit_order(counts)
        return kernel._experts_pallas(x, weight_of, order, n_touched, gate, up, down, tile_f=row.tile_f,
                                      vmem_limit_bytes=row.vmem_limit_bytes, interpret=False)

    text = jax.jit(call).lower(
        shape((rows, h), jnp.bfloat16), shape((held, rows), jnp.float32), shape((held,), jnp.int32),
        shape((held, h, f), jnp.bfloat16), shape((held, h, f), jnp.bfloat16), shape((held, f, h), jnp.bfloat16),
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
    assert not [ln for ln in text.splitlines() if " copy(" in ln and ("[16,6144,2048]" in ln or "[16,2048,6144]" in ln)]


def test_the_latent_kernel_compiles_for_a_v5e_at_longcat_flash_widths(one_chip):
    """64 slots of 64 heads over 6,144 pages of 16 rows 640 wide, a table
    of 96 pages, in bfloat16: Mosaic takes it, and the pool reaches the
    kernel as it is, with no copy in front of it."""
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def call(q_lat, pool, table, lengths):
        return latent_attention.latent_decode_attention(
            q_lat, pool, table, lengths, key_width=576, kv_rank=512, sm_scale=192 ** -0.5, interpret=False
        )

    text = jax.jit(call).lower(
        shape((64, 64, 640), jnp.bfloat16), shape((6144, 16, 640), jnp.bfloat16),
        shape((64, 96), jnp.int32), shape((64,), jnp.int32),
    ).compile().as_text()
    assert sum("custom-call" in ln and "latent_decode_attention" in ln for ln in text.splitlines()) == 1
    assert not [ln for ln in text.splitlines() if " copy(" in ln and "[6144,16,640]" in ln]


def test_the_recurrence_kernel_compiles_for_a_v5e_at_solar_open2_widths(one_chip):
    """64 slots of 64 heads of 128 x 128 float32 state (268 MB a layer): Mosaic
    takes it, the state is written in place over the donated operand, and
    nothing state-sized is copied or held beside it."""
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def call(state, q, k, v, g, beta):
        return kda_decode.kda_decode(state, q, k, v, g, beta, interpret=False)

    vec = shape((64, 64, 128), jnp.float32)
    compiled = jax.jit(call, donate_argnums=0).lower(
        shape((64, 64, 128, 128), jnp.float32), vec, vec, vec, vec, shape((64, 64), jnp.float32)).compile()
    text = compiled.as_text()
    assert sum("custom-call" in ln and "kda_decode" in ln for ln in text.splitlines()) == 1
    assert not [ln for ln in text.splitlines() if " copy(" in ln and "[64,64,128,128]" in ln]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 64 * 64 * 128 * 128 * 4 and memory.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", ["single step", "decode block"])
def test_the_latent_pool_is_never_laid_out_anew(one_chip, program):
    """The tiny LongCat-Flash preset in bfloat16 on 256 pages of 16, its
    decode program compiled for a v5e: the latent pool comes in row-major
    and no copy of a pool-sized operand is in the program.  (A row stored
    24 wide makes the chip's compact layout put the pages minor, and then
    each program lays every pool out row-major for its scatter and gather
    and back again.)"""
    compile_latent_decode(one_chip, program)


@pytest.mark.parametrize("program", ["single step", "decode block"])
def test_the_decode_programs_on_the_latent_kernel_gather_no_view(one_chip, program, monkeypatch):
    """The same programs on the latent kernel's lane (what a TPU backend
    takes): one kernel call an attention, no gathered view of the pages
    and still no copy of a pool.  On 4,096 pages: a pool of 256 pages (1
    MB) is small enough that the compiler prefetches it into VMEM for the
    kernel, which the chip's 126 MB pools never are."""
    monkeypatch.setattr(mla, "on_kernel_lane", lambda: True)
    monkeypatch.setattr(mla, "latent_decode_attention", functools.partial(mla.latent_decode_attention, interpret=False))
    text, cfg, paged, slots = compile_latent_decode(one_chip, program, num_pages=4096)
    calls = [ln for ln in text.splitlines() if "custom-call" in ln and "latent_decode_attention" in ln]
    assert len(calls) == cfg.num_layers
    view = f"[{slots},{paged.max_pages_per_seq},{paged.page_size},{cfg.mla.stored_width}]"
    assert not [ln for ln in text.splitlines() if view in ln]


def compile_latent_decode(one_chip, program, num_pages=256):
    """Compile the tiny preset's decode program for a v5e and check that
    the pools come in row-major and no program copies one."""
    family = families.load("longcat_flash")
    model_conf = {**MODEL, "torch_dtype": "bfloat16"}
    cfg, paged = family.build(model_conf, {"page_size": 16, "num_pages": num_pages, "max_pages_per_seq": 8})
    model = TransformerLM(dataclasses.replace(cfg, paged=paged), decode=True)
    slots = 4
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)  # noqa: E731
    params = jax.eval_shape(lambda w: family.params_tree(model_conf, w), weights.seed_words(1))
    cache = decode_cache_spec(model, slots)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    build = build_step_fn if program == "single step" else functools.partial(build_block_fn, T=4)
    fn = build(model, filtered=False, want_lp=False, derive_tables=True)
    text = fn.lower(
        jax.tree.map(place, params), jax.tree.map(place, cache), i32(slots, 1), i32(slots, 1),
        jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one_chip), i32(slots),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip), i32(slots, paged.max_pages_per_seq),
    ).compile().as_text()
    pool = (paged.num_pages, paged.page_size, cfg.mla.stored_width)
    assert cache["layer_0"]["attn"]["pool_latent"].shape == pool
    dims = "[" + ",".join(map(str, pool)) + "]{"
    # The module's entry layout (its first line): minor to major 2, 1, 0.
    entry = text.splitlines()[0]
    assert entry.count(dims) == 2 * cfg.num_layers and entry.count(dims + "2,1,0") == 2 * cfg.num_layers
    pool_sized = {int(np.prod(pool)), int(np.prod(pool[:2])) * cfg.mla.row_width}
    copies = []
    for line in text.splitlines():
        if not any(f" {op}(" in line for op in ("copy", "copy-start", "copy-done")):
            continue
        shape = line.split("=", 1)[1].split("]", 1)[0].split("[", 1)[1]
        if shape and int(np.prod([int(d) for d in shape.split(",")])) in pool_sized:
            copies.append(line.strip()[:160])
    assert not copies, copies
    return text, cfg, paged, slots
