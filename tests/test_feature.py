"""Feature / ShardTensor gather == numpy fancy-indexing oracle (reference
tests/python/cuda/test_shard_tensor.py:69-71, test_feature.py)."""

import numpy as np
import pytest

from quiver_tpu import (
    CSRTopo,
    DeviceConfig,
    Feature,
    ShardTensor,
    ShardTensorConfig,
)
from conftest import make_random_graph


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(7)
    return rng.standard_normal((500, 16)).astype(np.float32)


def test_shard_tensor_single_device(table):
    st = ShardTensor(0, ShardTensorConfig({}))
    st.append(table, 0)
    ids = np.array([0, 3, 499, 17, 3])
    np.testing.assert_allclose(np.asarray(st[ids]), table[ids])


def test_shard_tensor_device_plus_host(table):
    st = ShardTensor(0, ShardTensorConfig({}))
    st.append(table[:200], 0)
    st.append(table[200:], -1)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 500, 64)
    np.testing.assert_allclose(np.asarray(st[ids]), table[ids])
    assert st.shape == (500, 16)


def test_shard_tensor_multi_device(table):
    # stripes across the 8 fake CPU devices — exercises the ICI path shape
    st = ShardTensor(0, ShardTensorConfig({}))
    st.append(table[:150], 0)
    st.append(table[150:300], 1)
    st.append(table[300:], -1)
    ids = np.arange(0, 500, 7)
    np.testing.assert_allclose(np.asarray(st[ids]), table[ids])


def test_shard_tensor_from_cpu_tensor_budget(table):
    row_bytes = 16 * 4
    cfg = ShardTensorConfig({0: 100 * row_bytes, 1: 150 * row_bytes})
    st = ShardTensor.new_from_cpu_tensor(table, cfg)
    assert len(st.device_shards) == 2
    assert st.cpu_tensor is not None
    ids = np.array([0, 99, 100, 249, 250, 499])
    np.testing.assert_allclose(np.asarray(st[ids]), table[ids])


def test_feature_device_replicate(table):
    feat = Feature(rank=0, device_list=[0], device_cache_size=200 * 16 * 4)
    feat.from_cpu_tensor(table)
    ids = np.array([1, 199, 200, 499])
    np.testing.assert_allclose(np.asarray(feat[ids]), table[ids])


def test_feature_with_csr_topo_reorder(table):
    edge_index = make_random_graph(500, 4000, seed=9)
    topo = CSRTopo(edge_index=edge_index)
    feat = Feature(
        rank=0, device_list=[0], device_cache_size="10K", csr_topo=topo
    )
    feat.from_cpu_tensor(table)
    assert feat.feature_order is not None
    ids = np.array([5, 100, 250, 499, 0])
    np.testing.assert_allclose(np.asarray(feat[ids]), table[ids], rtol=1e-6)


def test_feature_clique_replicate(table):
    feat = Feature(
        rank=0,
        device_list=[0, 1],
        device_cache_size=100 * 16 * 4,
        cache_policy="p2p_clique_replicate",
    )
    feat.from_cpu_tensor(table)
    # striped across devices + host tail; gather still exact
    ids = np.arange(0, 500, 3)
    np.testing.assert_allclose(np.asarray(feat[ids]), table[ids])


def test_feature_lookup_padded_fully_resident(table):
    import jax.numpy as jnp

    feat = Feature(rank=0, device_list=[0], device_cache_size=500 * 16 * 4)
    feat.from_cpu_tensor(table)
    ids = jnp.asarray(np.array([3, 7, 11]))
    np.testing.assert_allclose(np.asarray(feat.lookup_padded(ids)), table[[3, 7, 11]])


def test_feature_ipc_shim_roundtrip(table):
    feat = Feature(rank=0, device_list=[0], device_cache_size=100 * 16 * 4)
    feat.from_cpu_tensor(table)
    handle = feat.share_ipc()
    feat2 = Feature.new_from_ipc_handle(0, handle)
    ids = np.array([0, 50, 150, 499])
    np.testing.assert_allclose(np.asarray(feat2[ids]), table[ids])


def test_feature_set_local_order_global_ids(table):
    # distributed path: this host owns global ids 10..19 only; lookups use
    # GLOBAL ids, so validity must come from the remap, not the local row
    # count (advisor finding: owned ids >= n_local were silently zeroed)
    local_rows = table[:10]
    owned_global = np.arange(10, 20, dtype=np.int64)
    feat = Feature(rank=0, device_list=[0], device_cache_size=10 * 16 * 4)
    feat.from_cpu_tensor(local_rows)
    feat.set_local_order(owned_global)
    np.testing.assert_allclose(
        np.asarray(feat[np.array([10, 15, 19])]), local_rows[[0, 5, 9]]
    )
    # unowned / out-of-range global ids yield zero rows, owned rows intact
    got = np.asarray(feat[np.array([3, 12, 10_000])])
    np.testing.assert_allclose(got[0], np.zeros(16))
    np.testing.assert_allclose(got[1], local_rows[2])
    np.testing.assert_allclose(got[2], np.zeros(16))


def test_feature_from_mmap(tmp_path, table):
    path = tmp_path / "feat.npy"
    np.save(path, table)
    mm = np.load(path, mmap_mode="r")
    feat = Feature.from_mmap(mm, DeviceConfig([0], 100 * 16 * 4))
    ids = np.array([0, 99, 100, 499])
    np.testing.assert_allclose(np.asarray(feat[ids]), table[ids])


def test_feature_bfloat16_tiers(table):
    # bfloat16 halves every in-memory tier: same cache BYTES hold 2x rows,
    # lookups return bf16 within rounding of the f32 source
    import jax.numpy as jnp

    cache_bytes = 100 * 16 * 4  # 100 f32 rows worth of bytes
    f32 = Feature(rank=0, device_list=[0], device_cache_size=cache_bytes)
    f32.from_cpu_tensor(table)
    bf16 = Feature(rank=0, device_list=[0], device_cache_size=cache_bytes,
                   dtype="bfloat16")
    bf16.from_cpu_tensor(table)
    assert f32.shard_tensor.device_shards[0][2].end == 100
    assert bf16.shard_tensor.device_shards[0][2].end == 200  # 2x rows hot
    assert bf16.shard_tensor.device_shards[0][1].dtype == jnp.bfloat16

    ids = np.array([0, 150, 250, 499])  # hot + cold mix
    got = np.asarray(bf16[ids]).astype(np.float32)
    np.testing.assert_allclose(got, table[ids], rtol=1e-2, atol=1e-2)

    # prefetch pipeline works in bf16 end to end
    from quiver_tpu.pipeline import TieredFeaturePipeline, tiered_lookup

    pipe = TieredFeaturePipeline(bf16)
    mapped, cold_rows, cold_pos = pipe.prepare(np.array([5, 450, 499]))
    out = np.asarray(
        tiered_lookup(pipe.hot_table, mapped, cold_rows, cold_pos)
    ).astype(np.float32)
    np.testing.assert_allclose(out, table[[5, 450, 499]], rtol=1e-2, atol=1e-2)


def test_feature_set_mmap_file(tmp_path, table):
    # reference feature.py:84-93 + disk-mask merge (feature.py:309-333):
    # the first 100 rows are cached in memory, the rest live on disk only
    path = tmp_path / "full.npy"
    np.save(path, table)
    feat = Feature(rank=0, device_list=[0], device_cache_size=100 * 16 * 4)
    feat.from_cpu_tensor(table[:100])  # in-memory tier holds rows 0..99
    disk_map = np.full(table.shape[0], -1, np.int64)
    disk_map[:100] = np.arange(100)  # cached ids -> their in-memory rows
    feat.set_mmap_file(str(path), disk_map)

    # read_mmap reads by global id
    np.testing.assert_allclose(
        np.asarray(feat.read_mmap(np.array([150, 499]))), table[[150, 499]]
    )
    # __getitem__ merges mem + disk tiers; out-of-range ids -> zero rows
    ids = np.array([5, 150, 99, 499, 1000])
    got = np.asarray(feat[ids])
    np.testing.assert_allclose(got[:4], table[ids[:4]], rtol=1e-6)
    np.testing.assert_allclose(got[4], np.zeros(16))


def test_lookup_padded_clip_semantics_direct(table):
    """Pin the jit path's out-of-range contract (feature.py _padded_gather):
    ids are silently jnp.clip'ed — negatives land on row 0, ids >= N on the
    LAST row. This is deliberate (a data-dependent raise cannot exist in an
    XLA program); validate_ids is the strict opt-in."""
    import jax.numpy as jnp

    feat = Feature(rank=0, device_list=[0], device_cache_size=500 * 16 * 4)
    feat.from_cpu_tensor(table)
    got = np.asarray(feat.lookup_padded(jnp.asarray(np.array([-5, 0, 499, 500, 10_000]))))
    np.testing.assert_allclose(got[0], table[0])     # negative -> row 0
    np.testing.assert_allclose(got[3], table[499])   # N -> last row
    np.testing.assert_allclose(got[4], table[499])   # >> N -> last row
    np.testing.assert_allclose(got[1:3], table[[0, 499]])


def test_lookup_padded_clip_semantics_remapped(table):
    """Same pin for the feature_order-remapped path (_padded_gather_ordered):
    the CLIP happens in ORIGINAL id space first, so an oob id resolves to
    the clamped original id's row — bit-identical to looking up id N-1.
    A wholly hot table has no degree reorder, so the order comes from
    `set_local_order` (a permutation: every global id is owned)."""
    import jax.numpy as jnp

    local_order = np.random.default_rng(3).permutation(500)
    feat = Feature(rank=0, device_list=[0], device_cache_size=500 * 16 * 4)
    feat.from_cpu_tensor(table[local_order])  # local row i holds global id local_order[i]
    feat.set_local_order(local_order)
    assert feat.feature_order is not None
    got = np.asarray(feat.lookup_padded(jnp.asarray(np.array([700, 499, -3, 0]))))
    np.testing.assert_allclose(got[0], table[499])  # oob -> clamped id 499's row
    np.testing.assert_allclose(got[1], table[499])
    np.testing.assert_allclose(got[2], table[0])    # negative -> id 0's row
    np.testing.assert_allclose(got[3], table[0])


OOB_IDS = np.array([5, 100, 250, 499, 0, -1, -7, 500, 10_000])


def assert_getitem_exact_and_zero_filled(feat, table):
    """`feat[ids]`: the host table's rows bit for bit, zeros out of range."""
    valid = (OOB_IDS >= 0) & (OOB_IDS < table.shape[0])
    got = np.asarray(feat[OOB_IDS])
    np.testing.assert_array_equal(got[valid], table[OOB_IDS[valid]])
    assert not got[~valid].any()


@pytest.mark.parametrize("policy", ["device_replicate", "p2p_clique_replicate"])
def test_wholly_hot_with_csr_topo_keeps_the_given_order(table, policy):
    """With every row in the hot tier there is no prefix to choose: no
    degree reorder, no `feature_order` (on the Feature or the CSRTopo), and
    the lookups return the host table's rows bit for bit — `lookup_padded`
    clipping out-of-range ids, `__getitem__` zero-filling them."""
    import jax.numpy as jnp

    from quiver_tpu import IciTopo

    topo = CSRTopo(edge_index=make_random_graph(500, 4000, seed=9))
    feat = Feature(rank=0, device_list=[0], device_cache_size=table.nbytes,
                   cache_policy=policy, csr_topo=topo)
    feat.topo = IciTopo(cliques=[[0]])  # a one-chip clique, as on one v5e
    feat.from_cpu_tensor(table)
    assert feat.feature_order is None and topo.feature_order is None
    assert feat.shard_tensor.cpu_tensor is None
    stored = np.asarray(feat.shard_tensor.device_shards[0][1])
    np.testing.assert_array_equal(stored, table)
    got = np.asarray(feat.lookup_padded(jnp.asarray(OOB_IDS)))
    np.testing.assert_array_equal(got, table[np.clip(OOB_IDS, 0, 499)])
    assert_getitem_exact_and_zero_filled(feat, table)


def test_wholly_hot_clique_stripes_keep_the_given_order(table):
    """`hot_total >= n` across the clique's chips (8 here) is wholly hot
    too: the stripes are slices of the table as given."""
    topo = CSRTopo(edge_index=make_random_graph(500, 4000, seed=9))
    feat = Feature(rank=0, device_list=[0, 1], device_cache_size=100 * 16 * 4,
                   cache_policy="p2p_clique_replicate", csr_topo=topo)
    assert len(feat.topo.get_clique(0)) * 100 >= 500
    feat.from_cpu_tensor(table)
    assert feat.feature_order is None and topo.feature_order is None
    for _, shard, off in feat.shard_tensor.device_shards:
        np.testing.assert_array_equal(np.asarray(shard), table[off.start:off.end])
    assert_getitem_exact_and_zero_filled(feat, table)


def test_disk_tier_keeps_the_reorder_with_a_whole_table_cache(tmp_path, table):
    """A disk tier keeps the degree reorder whatever the cache holds: the
    adaptive store moves rows between tiers later, by stored position."""
    topo = CSRTopo(edge_index=make_random_graph(500, 4000, seed=9))
    feat = Feature(rank=0, device_list=[0], device_cache_size=table.nbytes,
                   csr_topo=topo, disk_path=str(tmp_path / "tail.npy"))
    feat.from_cpu_tensor(table)
    assert feat.feature_order is not None and topo.feature_order is not None
    np.testing.assert_array_equal(np.asarray(feat[OOB_IDS[:5]]), table[OOB_IDS[:5]])


@pytest.mark.parametrize("program", ["_padded_gather", "_padded_gather_ordered"])
def test_gather_programs_lower_to_the_gathers_alone(program):
    """The programs `lookup_padded` launches hold one gather of the table
    (and one of `order`) and no select: `jnp.take`'s default mode="fill"
    brings a select over the whole output that XLA keeps on the chip even
    behind a clip (5 ms a step on igb-small's 1.7 GB of rows)."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import feature as feature_mod

    table = jax.ShapeDtypeStruct((500, 16), jnp.float32)
    order = jax.ShapeDtypeStruct((500,), jnp.int64)
    ids = jax.ShapeDtypeStruct((64,), jnp.int32)
    args = (table, ids) if program == "_padded_gather" else (table, order, ids)
    text = getattr(feature_mod, program).lower(*args).as_text()
    assert text.count('"stablehlo.gather"(') == len(args) - 1
    assert "select" not in text


def test_validate_ids_opt_in(table):
    """The strict helper: raises naming the bad count/examples where the
    lookup paths stay silent — both the direct and the local-order paths."""
    import pytest

    feat = Feature(rank=0, device_list=[0], device_cache_size=500 * 16 * 4)
    feat.from_cpu_tensor(table)
    ok = feat.validate_ids(np.array([0, 17, 499]))
    assert ok.dtype == np.int64 and ok.tolist() == [0, 17, 499]
    with pytest.raises(ValueError, match=r"2 of 4 .*examples: \[-1, 500\]"):
        feat.validate_ids(np.array([-1, 0, 500, 499]))

    # distributed remap: unowned globals are invalid even when in range
    dist = Feature(rank=0, device_list=[0], device_cache_size=10 * 16 * 4)
    dist.from_cpu_tensor(table[:10])
    dist.set_local_order(np.arange(10, 20, dtype=np.int64))
    dist.validate_ids(np.array([10, 19]))
    with pytest.raises(ValueError, match="owned global ids"):
        dist.validate_ids(np.array([3, 12]))  # 3 is in [0, map) but unowned
    with pytest.raises(ValueError, match="owned global ids"):
        dist.validate_ids(np.array([10_000]))


def test_native_gather_rows_any_dtype():
    """The byte-row native gather serves every C-contiguous dtype (the
    reference kernel is float32-only, quiver_feature.cu:65-69); bf16 cold
    tiers ride the native path instead of numpy fancy indexing. OOB ids
    return zero rows in all dtypes."""
    import jax.numpy as jnp

    from quiver_tpu.ops.cpu_kernels import gather_rows, native_available

    from quiver_tpu.ops.cpu_kernels import _load_native

    rng = np.random.default_rng(0)
    ids = np.array([3, 0, 7, -1, 12, 5], np.int64)
    for dtype in (np.float32, np.float64, np.int32, jnp.bfloat16):
        table = rng.standard_normal((10, 5)).astype(dtype)
        got = gather_rows(table, ids)
        assert got.dtype == table.dtype
        for i, idx in enumerate(ids):
            if 0 <= idx < 10:
                np.testing.assert_array_equal(got[i], table[idx])
            else:
                assert (np.asarray(got[i], np.float64) == 0).all()


def test_gather_rows_fallback_same_contract():
    """The numpy fallback (non-contiguous table, so the native engine is
    skipped) shares the native paths' contract: OOB ids — negative or
    >= N — yield zero rows, never IndexError, never end-relative wrap."""
    from quiver_tpu.ops.cpu_kernels import gather_rows

    rng = np.random.default_rng(1)
    base = rng.standard_normal((10, 8)).astype(np.float32)
    table = base[:, ::2]  # non-contiguous view: forces the numpy fallback
    assert not table.flags.c_contiguous
    ids = np.array([2, -1, 9, 10, -3, 0], np.int64)
    got = gather_rows(table, ids)
    assert got.shape == (6, 4)
    for i, idx in enumerate(ids):
        if 0 <= idx < 10:
            np.testing.assert_array_equal(got[i], table[idx])
        else:
            # -1/-3 must be ZERO rows (not wrap to table[9]/table[7])
            assert (got[i] == 0).all()


def test_gather_rows_zero_row_table_both_paths():
    """Degenerate zero-row table (e.g. an empty cold tier): every id is out
    of range, so the contract demands all-zero rows on EVERY path. The
    numpy fallback used to IndexError here — its np.where(ok, ids, 0)
    rewrite still indexes row 0 of an empty table (ADVICE.md round 5)."""
    from quiver_tpu.ops import cpu_kernels
    from quiver_tpu.ops.cpu_kernels import gather_rows

    ids = np.array([0, 3, -1], np.int64)
    for dtype in (np.float32, np.int32):
        empty = np.zeros((0, 5), dtype)
        # whatever engine is loaded (native or fallback)
        got = gather_rows(empty, ids)
        assert got.shape == (3, 5) and got.dtype == dtype and (got == 0).all()
        # the numpy fallback explicitly (a C-contiguous zero-row table
        # would otherwise ride the native path when the .so is present)
        saved = cpu_kernels._LIB, cpu_kernels._LIB_TRIED
        cpu_kernels._LIB, cpu_kernels._LIB_TRIED = None, True
        try:
            got = gather_rows(empty, ids)
        finally:
            cpu_kernels._LIB, cpu_kernels._LIB_TRIED = saved
        assert got.shape == (3, 5) and got.dtype == dtype and (got == 0).all()


def test_out_of_range_device_rank_is_an_error():
    """device_list=[0, 1, 2, 3] on a one-chip machine used to wrap every
    shard onto chip 0 (rank % n) without a word."""
    import jax

    from quiver_tpu.pyg import GraphSageSampler

    n_dev = len(jax.local_devices())
    arr = np.zeros((4, 3), np.float32)
    st = ShardTensor(0, ShardTensorConfig({}))
    st.append(arr, n_dev - 1)  # the last real device is fine
    with pytest.raises(ValueError, match="out of range"):
        st.append(arr, n_dev)
    with pytest.raises(ValueError, match="out of range"):
        Feature(rank=n_dev, device_list=[n_dev], device_cache_size="1M").from_cpu_tensor(arr)
    topo = CSRTopo(edge_index=make_random_graph(20, 60))
    with pytest.raises(ValueError, match="out of range"):
        GraphSageSampler(topo, [2], device=n_dev, mode="TPU")
