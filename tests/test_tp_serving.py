"""Tensor-parallel serving: mesh derivation + the sharding contract.

Tier-1 discipline (ISSUE 6 / the conftest budget guard): shape/spec
units only — no engine steps, no model compiles (the one exception is
the pair of small cache-writer programs the last test builds on the
mesh).  The engine constructions here reuse the session-scoped ``shared_engine`` fixture's
already-initialized params (ctor placement is ``device_put`` +
``eval_shape``, which compile nothing); the step/prefill programs stay
unbuilt because the engine is never stepped.  The full tp=2 serving run
(bit-identical streams, preempt/resume, overlap discards) lives in
``__graft_entry__.dryrun_multichip`` — the multichip harness, not
tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_device_plugin_tpu.parallel.mesh import (
    allocated_chip_indices,
    mesh_from_allocation,
    snake_order,
)
from k8s_device_plugin_tpu.parallel.serving import (
    assert_explicit_sharding,
    cache_leaf_spec,
    cache_sharding,
)


def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


# --------------------------------------------------------- mesh derivation


def test_snake_order_walks_ici_neighbors():
    # 2x2 plane: row-major is 0,1,2,3 but 1->2 is a diagonal hop; the
    # snake 0,1,3,2 keeps every consecutive pair one ICI link apart.
    assert snake_order((2, 2, 1)) == [0, 1, 3, 2]
    # 2x4 (v5e/v6e full host): serpentine through the four rows.
    assert snake_order((2, 4, 1)) == [0, 1, 3, 2, 4, 5, 7, 6]
    # Chains are identity.
    assert snake_order((4, 1, 1)) == [0, 1, 2, 3]


def test_allocated_chip_indices_parses_plugin_env():
    assert allocated_chip_indices({"TPU_VISIBLE_CHIPS": "1,3"}) == [1, 3]
    assert allocated_chip_indices({}) is None
    assert allocated_chip_indices({"TPU_VISIBLE_CHIPS": "junk"}) is None


def test_mesh_from_allocation_follows_ici_order():
    devices = jax.devices()[:4]
    env = {"TPU_VISIBLE_CHIPS": "0,1,2,3", "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    mesh = mesh_from_allocation(4, environ=env, devices=devices)
    assert dict(mesh.shape) == {"tp": 4}
    got = list(mesh.devices.flat)
    assert got == [devices[i] for i in (0, 1, 3, 2)]


def test_mesh_from_allocation_mismatch_names_both():
    env = {"TPU_VISIBLE_CHIPS": "0,1,2,3", "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    with pytest.raises(ValueError) as exc:
        mesh_from_allocation(2, environ=env, devices=jax.devices()[:4])
    msg = str(exc.value)
    assert "--tp 2" in msg and "4 chip" in msg


def test_mesh_from_allocation_off_cluster_fallback():
    mesh = mesh_from_allocation(2, environ={}, devices=jax.devices()[:4])
    assert dict(mesh.shape) == {"tp": 2}
    assert list(mesh.devices.flat) == jax.devices()[:2]
    with pytest.raises(ValueError):
        mesh_from_allocation(99, environ={})


# ------------------------------------------------------- sharding contract


def test_cache_leaf_specs():
    pool = jax.ShapeDtypeStruct((8, 4, 2, 16), jnp.float32)
    scale = jax.ShapeDtypeStruct((8, 4, 2), jnp.float32)
    table = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    assert cache_leaf_spec("layer_0/attn/pool_key", pool, 2) == P(
        None, None, "tp", None
    )
    assert cache_leaf_spec("layer_0/attn/pool_value_scale", scale, 2) == P(
        None, None, "tp"
    )
    assert cache_leaf_spec("layer_0/attn/page_table", table, 2) == P()
    assert cache_leaf_spec("layer_0/attn/seq_lens", table, 2) == P()
    # tp=1 never shards anything.
    assert cache_leaf_spec("layer_0/attn/pool_key", pool, 1) == P()


def test_cache_leaf_spec_refuses_indivisible_pool():
    pool = jax.ShapeDtypeStruct((8, 4, 3, 16), jnp.float32)
    with pytest.raises(ValueError, match="pool_key"):
        cache_leaf_spec("layer_0/attn/pool_key", pool, 2)


def test_cache_sharding_tree():
    mesh = _mesh2()
    cache = {
        "layer_0": {
            "attn": {
                "pool_key": jax.ShapeDtypeStruct((8, 4, 2, 16), jnp.float32),
                "page_table": jax.ShapeDtypeStruct((2, 8), jnp.int32),
            }
        }
    }
    sh = cache_sharding(cache, mesh)
    assert sh["layer_0"]["attn"]["pool_key"].spec == P(None, None, "tp", None)
    assert sh["layer_0"]["attn"]["page_table"].spec == P()


def test_coverage_lint_passes_and_names_offenders():
    mesh = _mesh2()
    rep = NamedSharding(mesh, P())
    pool_sh = NamedSharding(mesh, P(None, None, "tp", None))
    pool = jax.device_put(jnp.zeros((8, 4, 2, 16)), pool_sh)
    lens = jax.device_put(jnp.zeros((2,), jnp.int32), rep)
    good = {"cache": {"pool_key": pool, "seq_lens": lens}}
    assert assert_explicit_sharding(good, mesh) == 2

    # A leaf left on one device (no explicit placement) fails by path.
    stray = {"cache": {"pool_key": pool, "seq_lens": jnp.zeros((2,), jnp.int32)}}
    with pytest.raises(AssertionError, match="seq_lens"):
        assert_explicit_sharding(stray, mesh)

    # A silently replicated pool fails by path even though it IS placed.
    fat = {"cache": {"pool_key": jax.device_put(jnp.zeros((8, 4, 2, 16)), rep)}}
    with pytest.raises(AssertionError, match="REPLICATED"):
        assert_explicit_sharding(fat, mesh)


# ----------------------------------------------- engine construction (spec)


def test_engine_ctor_places_state_and_reports_tp(shared_engine):
    """Sharded construction end to end without stepping: params, cache,
    chain, and the rebuilt device state all land on the mesh with
    explicit specs, and the tp surface (debug_state block, gauge) shows
    the degree.  No jit programs are built — the engine is never
    stepped."""
    from k8s_device_plugin_tpu.models.engine import EngineMetrics, ServingEngine
    from k8s_device_plugin_tpu.models.transformer import PagedConfig
    from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry

    cfg, params, _ = shared_engine
    mesh = _mesh2()
    registry = MetricsRegistry()
    paged = PagedConfig(page_size=4, num_pages=16, max_pages_per_seq=8)
    eng = ServingEngine(
        cfg, params, paged, max_slots=2,
        metrics=EngineMetrics(registry), mesh=mesh,
    )
    assert eng.tp_size == 2
    checked = eng.assert_sharded()
    assert checked > 0
    # The KV pools really shard: half the kv heads per device.
    pool = eng.cache["layer_0"]["attn"]["pool_key"]
    shard = pool.sharding.shard_shape(pool.shape)
    assert shard[2] * 2 == pool.shape[2]
    # A state rebuild re-applies the contract (replicated step dict).
    dev = eng._device_state()
    assert set(dev["tokens"].sharding.device_set) == set(mesh.devices.flat)
    assert eng.assert_sharded() == checked + 5  # + tokens/positions/temps/aids/key
    state = eng.debug_state()
    assert state["tp"]["size"] == 2 and state["tp"]["mesh"] == {"tp": 2}
    assert "tpu_engine_tp_size 2" in registry.render()


def test_kernel_engine_sharding_contract_survives_split_k(shared_engine):
    """The split-K kernel rework (ISSUE 13) changes HOW pages are read,
    not the cache layout: a use_kernel=True engine (with a pinned split
    degree) built sharded must satisfy the same per-leaf contract — KV
    pools partitioned on the kv-heads axis, table/chain replicated —
    with every leaf covered (the kernel's page blocks then stream each
    chip's own head shard; no new leaf escapes the lint).  Ctor-only:
    no jit programs are built."""
    from k8s_device_plugin_tpu.models.engine import ServingEngine
    from k8s_device_plugin_tpu.models.transformer import PagedConfig

    cfg, params, _ = shared_engine
    paged = PagedConfig(
        page_size=4, num_pages=16, max_pages_per_seq=8,
        use_kernel=True, kernel_num_splits=2,
    )
    eng = ServingEngine(cfg, params, paged, max_slots=2, mesh=_mesh2())
    assert eng.kernel_on
    assert eng.assert_sharded() > 0
    for pool in ("pool_key", "pool_value"):
        leaf = eng.cache["layer_0"]["attn"][pool]
        shard = leaf.sharding.shard_shape(leaf.shape)
        assert shard[2] * 2 == leaf.shape[2], pool
    state = eng.debug_state()
    assert state["config"]["kernel"] is True
    assert state["config"]["kernel_splits"] == 2


def test_cache_writers_keep_the_sharding_contract(shared_engine):
    """A graft and a clear on the mesh go through the compiled, donated
    cache writers (engine_paging._cache_write), whose outputs are pinned
    to the shardings the tree came with: the lint still passes, the
    pools are still partitioned on the kv-heads axis (no silent
    replication by the new programs), and the bytes are the unsharded
    engine's.  The dense cache is random data: no model program is
    built."""
    from k8s_device_plugin_tpu.models.engine import ServingEngine
    from k8s_device_plugin_tpu.models.transformer import (
        PagedConfig,
        decode_cache_spec,
    )

    cfg, params, _ = shared_engine
    paged = PagedConfig(page_size=4, num_pages=16, max_pages_per_seq=8)
    sharded = ServingEngine(cfg, params, paged, max_slots=2, mesh=_mesh2())
    plain = ServingEngine(cfg, params, paged, max_slots=2)
    checked = sharded.assert_sharded()
    spec = decode_cache_spec(sharded._dense_chunk_model(8), 2)
    rng = np.random.default_rng(0)
    dense = jax.tree.map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), s.dtype), spec
    )
    for eng in (sharded, plain):
        eng._graft(1, dense, [3, 7, 5], 6, 0, row_idx=1)
        eng._graft(0, dense, [9, 2], 8, 1)
        eng._clear_slot(0)
    assert sharded.assert_sharded() == checked
    for name in sharded._layer_names:
        for leaf_name in ("pool_key", "pool_value", "seq_lens"):
            leaf = sharded.cache[name]["attn"][leaf_name]
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(plain.cache[name]["attn"][leaf_name])
            )
            if leaf_name != "seq_lens":
                shard = leaf.sharding.shard_shape(leaf.shape)
                assert shard[2] * 2 == leaf.shape[2], (name, leaf_name)
    np.testing.assert_array_equal(
        np.asarray(sharded._chain), np.asarray(plain._chain)
    )
    assert np.asarray(sharded._chain)[1].tolist() == [3, 7, 5, 0, 0, 0, 0, 0]


def test_engine_ctor_rejects_indivisible_kv_heads(shared_engine):
    from k8s_device_plugin_tpu.models.engine import ServingEngine
    from k8s_device_plugin_tpu.models.transformer import PagedConfig

    cfg, params, _ = shared_engine
    # tiny() has 4 (kv) heads; an 8-way axis cannot divide them.  The
    # ctor must refuse BEFORE any placement with an error naming both.
    mesh = Mesh(np.array(jax.devices()[:8]), ("tp",))
    paged = PagedConfig(page_size=4, num_pages=16, max_pages_per_seq=8)
    with pytest.raises(ValueError, match="kv.heads|kv_heads"):
        ServingEngine(cfg, params, paged, max_slots=2, mesh=mesh)
    # And an axis name the mesh lacks is named too.
    with pytest.raises(ValueError, match="no 'tp' axis"):
        ServingEngine(
            cfg, params, paged, max_slots=2,
            mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)),
        )


def test_unsharded_engine_unchanged(shared_engine):
    """The default path carries no mesh: tp block reports size 1 and the
    lint refuses to run (nothing to check)."""
    _, _, eng = shared_engine
    assert eng.tp_size == 1
    state = eng.debug_state()
    assert state["tp"] == {
        "size": 1, "axis": None, "mesh": None, "devices": None,
    }
    with pytest.raises(ValueError, match="no mesh"):
        eng.assert_sharded()
