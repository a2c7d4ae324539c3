"""The compiled cache writers (models/engine_paging.py): the prefill graft
and the slot-row write go through one donated program per operation.

The oracle for the graft is a plain numpy copy: every private covered
page holds exactly the dense rows with a zero tail, and every other byte
of every pool — shared pages included — is what it was.  The engines
here are never stepped: the dense cache is random data of the right
shape, so each case costs one tiny writer compile and no model compile.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
from k8s_device_plugin_tpu.models.transformer import (
    GPTConfig,
    PagedConfig,
    TransformerLM,
    decode_cache_spec,
)
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

PS, BUCKET, BATCH = 4, 16, 2
KINDS = ("bf16", "int8", "spec", "mixer")
PAGED = PagedConfig(page_size=PS, num_pages=24, max_pages_per_seq=8)


def _random_like(tree, seed):
    """Random contents for every leaf of a cache tree (ints and floats
    alike), so "unchanged" and "copied" are both visible."""
    rng = np.random.default_rng(seed)

    def fill(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return jnp.asarray(
                rng.integers(-100, 100, leaf.shape), leaf.dtype
            )
        return jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)

    return jax.tree.map(fill, tree)


@pytest.fixture(scope="module")
def engines():
    """kind -> (engine, registry): bf16 pools, int8 KV with scale pools,
    a speculative engine (host-published page_table rows), and a model
    with a mixer (per-slot ``slot_*`` leaves beside the pools).  Module
    scope: the writers compile once a kind."""
    from k8s_device_plugin_tpu.models.ssm import MambaConfig
    from k8s_device_plugin_tpu.ops.quant import quantize_lm_params

    out = {}
    for kind in KINDS:
        cfg = dataclasses.replace(
            GPTConfig.tiny(),
            max_seq=32,
            dtype=jnp.bfloat16 if kind == "bf16" else jnp.float32,
            quant_kv=kind == "int8",
            mixer=MambaConfig(d_ssm=64, n_heads=4, head_dim=16, d_state=16, n_groups=2, chunk_size=8)
            if kind == "mixer" else None,
        )
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        registry = MetricsRegistry()
        extra = (
            {"spec_gamma": 2, "draft_params": quantize_lm_params(params)}
            if kind == "spec"
            else {}
        )
        out[kind] = (
            ServingEngine(
                cfg, params, PAGED, max_slots=2,
                metrics=EngineMetrics(registry), **extra,
            ),
            registry,
        )
    return out


def _dense(eng, bucket=BUCKET, batch=BATCH, seed=1):
    return _random_like(
        decode_cache_spec(eng._dense_chunk_model(bucket), batch), seed
    )


def _host(tree):
    return jax.tree.map(np.asarray, tree)


@pytest.mark.parametrize("n_shared", [0, 1])
@pytest.mark.parametrize("plen", [8, 6, BUCKET], ids=["edge", "mid", "bucket"])
@pytest.mark.parametrize("kind", KINDS)
def test_graft_writes_private_pages_and_nothing_else(engines, kind, plen, n_shared):
    eng, _ = engines[kind]
    eng.cache = _random_like(eng.cache, seed=7)
    dense = _dense(eng)
    before, src = _host(eng.cache), _host(dense)
    chain_before = np.asarray(eng._chain)
    slot, row_idx = 1, 1
    n_cover = math.ceil(plen / PS)
    pages = [5, 9, 3, 17, 11, 20][: n_cover + 1]  # one generation page
    eng._graft(slot, dense, pages, plen, n_shared, row_idx=row_idx)
    after = _host(eng.cache)

    pools = [p for p in before["layer_0"]["attn"] if p.startswith("pool_")]
    assert ("pool_key_scale" in pools) == (kind == "int8")
    for name in eng._layer_names:
        att0, att1, d = before[name]["attn"], after[name]["attn"], src[name]["attn"]
        for pool in pools:
            rows = d["cached_" + pool[len("pool_"):]][row_idx].copy()
            rows[plen:] = 0
            want = att0[pool].copy()
            for j in range(n_shared, n_cover):
                want[pages[j]] = rows[j * PS : (j + 1) * PS]
            assert att1[pool].dtype == att0[pool].dtype
            np.testing.assert_array_equal(att1[pool], want, err_msg=f"{name}/{pool}")
        lens = att0["seq_lens"].copy()
        lens[slot] = plen
        np.testing.assert_array_equal(att1["seq_lens"], lens)
        table = att0["page_table"].copy()
        if kind == "spec":
            n_publish = min((plen + 2) // PS + 1, len(pages))
            table[slot] = 0
            table[slot, :n_publish] = pages[:n_publish]
            assert eng._slot_visible[slot] == n_publish
        np.testing.assert_array_equal(att1["page_table"], table)
        # The second kind of cached unit: a per-slot leaf takes the
        # dense cache's whole row; a model without a mixer has none, and
        # its layers hold what they held.
        assert set(after[name]) == ({"attn", "mixer"} if kind == "mixer" else {"attn"})
        for leaf in ("slot_ssm", "slot_conv") if kind == "mixer" else ():
            want = before[name]["mixer"][leaf].copy()
            want[slot] = src[name]["mixer"][leaf][row_idx]
            np.testing.assert_array_equal(after[name]["mixer"][leaf], want, err_msg=f"{name}/{leaf}")
    assert (eng.slot_state_bytes > 0) == (kind == "mixer")
    chain = chain_before.copy()
    if kind != "spec":
        chain[slot] = 0
        chain[slot, : len(pages)] = pages
    np.testing.assert_array_equal(np.asarray(eng._chain), chain)


@pytest.mark.parametrize("kind", KINDS)
def test_a_kv_pool_stores_the_row_the_model_defines(engines, kind):
    """K/V pools keep their [pages, page_size, kv_heads, head_dim] shape
    and hold no padding: the bytes a cached position takes are the K and
    V rows (and int8 scales) of every layer, and the pad gauge reads 0
    (only a latent row is stored lane-aligned, models/mla.py)."""
    eng, registry = engines[kind]
    cfg = eng.cfg
    att = eng.cache["layer_0"]["attn"]
    shape = (PAGED.num_pages, PS, cfg.kv_heads, cfg.head_dim)
    assert att["pool_key"].shape == att["pool_value"].shape == shape
    itemsize = {"bf16": 2, "int8": 1}.get(kind, 4)
    scales = 2 * cfg.kv_heads * 4 if kind == "int8" else 0
    assert eng.cache_bytes_per_token == cfg.num_layers * (2 * cfg.kv_heads * cfg.head_dim * itemsize + scales)
    assert eng.cache_pad_bytes_per_token == 0
    for series, want in (("tpu_engine_cache_bytes_per_token", eng.cache_bytes_per_token),
                         ("tpu_engine_cache_pad_bytes_per_token", 0)):
        [line] = [l for l in registry.render().splitlines() if l.startswith(series + " ")]
        assert float(line.split()[-1]) == want


def test_prompt_lengths_add_no_writer_and_one_dispatch_a_prompt(engines):
    """Two prompts of different lengths (and shared-page counts) in one
    bucket run the SAME compiled writer: the programs gauge stays put
    and ``dispatches{op="graft"}`` rises by one a prompt.  Another
    (batch, bucket) shape is what adds a program."""
    eng, registry = engines["bf16"]

    def metric(series):
        [line] = [
            l for l in registry.render().splitlines() if l.startswith(series + " ")
        ]
        return float(line.split()[-1])

    def gauge():
        return metric("tpu_engine_cache_write_programs")

    def grafts():
        return metric('tpu_engine_cache_write_dispatches_total{op="graft"}')

    dense = _dense(eng)
    eng._graft(0, dense, [4, 5, 6], 7, 0)
    programs, n = gauge(), grafts()
    assert programs == eng.cache_write_programs() >= 1
    eng._graft(1, dense, [7, 8, 9, 10, 11], 16, 1, row_idx=1)
    eng._graft(0, dense, [12], 1, 0)
    assert gauge() == programs
    assert grafts() == n + 2 == eng.cache_write_dispatches["graft"]
    eng._graft(0, _dense(eng, bucket=8, batch=1), [4, 5], 5, 0)
    assert gauge() == programs + 1
    # These engines never decode: no page is grown, so the chain writer
    # (optimistic admission's third kind of program) is never built.
    assert eng.cache_writes_state() == {
        "dispatches": dict(eng.cache_write_dispatches),
        "programs": int(programs) + 1,
        "chain": {"dispatches": 0, "pages": 0},
    }


def test_short_bucket_below_a_page(engines):
    """A prompt bucket smaller than a page (bucket 2, page 4): the dense
    row is padded up to one whole page."""
    eng, _ = engines["bf16"]
    eng.cache = _random_like(eng.cache, seed=3)
    dense = _dense(eng, bucket=2, batch=1)
    before = _host(eng.cache)
    eng._graft(0, dense, [6, 7], 2, 0)
    for name in eng._layer_names:
        want = before[name]["attn"]["pool_key"].copy()
        want[6] = 0
        want[6, :2] = np.asarray(dense[name]["attn"]["cached_key"])[0]
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name]["attn"]["pool_key"]), want
        )


def _lens_and_row(eng, slot):
    att = eng.cache["layer_1"]["attn"]
    row = eng._chain if eng._derive_tables else att["page_table"]
    return int(att["seq_lens"][slot]), np.asarray(row)[slot].tolist()


@pytest.mark.parametrize("kind", ["bf16", "spec", "mixer"])
def test_clear_slot_is_one_dispatch_of_the_slot_writer(engines, kind):
    eng, _ = engines[kind]
    eng.cache = _random_like(eng.cache, seed=5)
    eng._graft(1, _dense(eng), [5, 9, 3], 8, 0)
    assert _lens_and_row(eng, 1) == (8, [5, 9, 3, 0, 0, 0, 0, 0])
    pools = _host(eng.cache)
    n, programs = eng.cache_write_dispatches["slot"], eng.cache_write_programs()
    eng._clear_slot(1)  # the slot holds no host pages: nothing to release
    assert eng.cache_write_dispatches["slot"] == n + 1
    assert _lens_and_row(eng, 1) == (0, [0] * 8)
    assert eng._slot_visible[1] == 0
    for name in eng._layer_names if kind == "mixer" else ():
        for leaf, was in pools[name]["mixer"].items():  # the slot's rows zeroed, in that one dispatch
            now = np.asarray(eng.cache[name]["mixer"][leaf])
            assert not now[1].any() and was[1].any()
            np.testing.assert_array_equal(now[0], was[0])
    eng._clear_slot(0)
    assert eng.cache_write_programs() <= programs + 1  # built once, then reused
    for name in eng._layer_names:  # a slot write touches no pool
        for pool in ("pool_key", "pool_value"):
            np.testing.assert_array_equal(
                np.asarray(eng.cache[name]["attn"][pool]),
                pools[name]["attn"][pool],
            )


@pytest.fixture()
def tiered(shared_engine):
    """The session's compiled engine with both KV tiers on, put back as
    the kvcache and handoff suites put it back."""
    cfg, params, eng = shared_engine
    eng._kv_retain = True
    eng._kv_arena.budget_bytes = 8 << 20
    try:
        yield eng
    finally:
        eng.role = "unified"
        eng._handoff_skip_covered = False
        eng._optimistic = False
        eng._kv_retain = False
        eng.kvcache_clear()
        eng._kv_arena.budget_bytes = 0
        assert len(eng.free_pages) == eng.paged.num_pages - 1


def test_set_slot_serves_clear_restore_resume_and_handoff_admit(tiered, monkeypatch):
    """The slot-row writer through its three callers on one engine: a
    finish (clear), a preempted request resumed by restore, and a
    decode-role admit from shipped logits.  The two admits prefill
    nothing, so every one of their device writes is a ``slot``
    dispatch, and the streams are the undisturbed ones."""
    eng = tiered
    writes = eng.cache_write_dispatches

    # 1. clear: one request, one graft, one teardown.
    g0, s0 = writes["graft"], writes["slot"]
    jobs = [([3, 141, 59], 6), ([9, 10], 6)]
    refs = [eng.run([job])[0].tokens for job in jobs]
    assert (writes["graft"] - g0, writes["slot"] - s0) == (2, 2)

    # 2. restore-resume: starve the pool so growth preempts, as the
    #    kvcache suite does; every resume is a slot write, no graft.
    eng.kvcache_clear()
    eng._optimistic = True
    with eng._lock:
        parked = [eng.free_pages.pop() for _ in range(len(eng.free_pages) - 3)]
    g0, s0, res0, pre0 = (
        writes["graft"], writes["slot"], eng.kv_resumes_restored, eng.preemptions,
    )
    subs = [eng.submit(p, n) for p, n in jobs]
    try:
        for _ in range(4000):
            if all(r.done for r in subs):
                break
            eng.step()
    finally:
        eng._optimistic = False
        with eng._lock:
            eng.kvcache_clear()
            for page in parked:
                eng.free_pages.append(page)
    assert [r.tokens for r in subs] == refs
    resumed, preempted = eng.kv_resumes_restored - res0, eng.preemptions - pre0
    assert resumed > 0 and eng.kv_resumes_recompute == 0
    assert writes["graft"] - g0 == len(jobs)  # first admissions only
    # One write a teardown (two finishes, each preemption) and a resume.
    assert writes["slot"] - s0 == len(jobs) + preempted + resumed

    # 3. handoff admit: a page-aligned prompt whose pages are retained
    #    and whose last-position logits were shipped admits with no
    #    prefill job at all.
    prompt = [3, 141, 59, 7, 11, 5, 9, 2]
    seen = []
    orig = eng._sample_first_token
    monkeypatch.setattr(
        eng, "_sample_first_token",
        lambda req, lg: (seen.append(np.asarray(lg)), orig(req, lg))[1],
    )
    ref = eng.run([(prompt, 5)])[0].tokens
    with eng._lock:
        eng._kv_arena.put(
            ("logits", -1, tuple(prompt)), {"logits": seen[0]}, seen[0].nbytes
        )
    eng.role = "decode"
    eng._handoff_skip_covered = True
    g0, s0, adm0 = writes["graft"], writes["slot"], eng.handoff_noprefill_admits
    got = eng.run([(prompt, 5)])[0].tokens
    assert got == ref
    assert eng.handoff_noprefill_admits == adm0 + 1
    assert (writes["graft"] - g0, writes["slot"] - s0) == (0, 2)  # admit, clear
