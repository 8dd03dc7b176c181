"""Placement shard by shard, the layout resolved from the graph, and what a
sharded step sampled (ISSUE 28): `shard_feature_rows` / `shard_topology_rows`
never hand a device more than its own block and never build the stack on the
host; ``layout=None`` picks the flat layout for a low-degree graph and the
tile layout for a products-like one, with the same draws from the same key;
`make_sharded_topo_sample` returns the samples and rows the train step
trained on, held here against the host CSR, the host table and the
one-device step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from quiver_tpu import CSRTopo, trace as qtrace
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops.sample import LANE, flat_resolve
from quiver_tpu.parallel import (
    ShardedTopology,
    TiledShardedTopology,
    make_mesh,
    make_sharded_topo_sample,
    make_sharded_topo_train_step,
    pad_to_multiple,
    replicate,
    resolve_topology_layout,
    sampling_comm_bytes,
    shard_feature_hot_cold,
    shard_feature_rows,
    shard_topology_rows,
    step_comm_bytes,
)
from quiver_tpu.parallel.topology import (
    TILE_SLOTS_PER_EDGE_MAX,
    build_tiled_topology_shards,
    build_topology_shards,
    tile_slots_per_edge,
)

SIZES = (4, 3)


def graph(n, mean_degree, seed=0):
    """Skewed out-degrees around ``mean_degree``, every node at least 1."""
    rng = np.random.default_rng(seed)
    deg = np.maximum((rng.pareto(2.0, n) * mean_degree).astype(np.int64), 1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return CSRTopo(indptr=indptr, indices=rng.integers(0, n, int(indptr[-1])))


@pytest.fixture
def transfers(monkeypatch):
    """Bytes of every host array handed to `jax.device_put`, in order."""
    seen, real = [], jax.device_put

    def spy(x, *args, **kwargs):
        seen.extend(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(x)
                    if isinstance(leaf, np.ndarray))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


class SlicedOnly:
    """A table that can be sliced by rows and nothing else: asking for the
    whole of it (``np.asarray``) is an error."""

    def __init__(self, data):
        self.data, self.shape, self.dtype, self.asked = data, data.shape, data.dtype, []

    def __getitem__(self, rows):
        assert isinstance(rows, slice) and rows.step is None
        self.asked.append(rows.stop - rows.start)
        return self.data[rows]

    def __array__(self, *args, **kwargs):
        raise AssertionError("the whole table was asked for")


@pytest.mark.parametrize("n", [4000, 4001, 4003])
def test_feature_rows_go_up_one_shard_at_a_time(n, transfers):
    mesh = make_mesh(4, dp=1)
    data = np.random.default_rng(1).standard_normal((n, 8)).astype(np.float32)
    table = SlicedOnly(data)
    placed = shard_feature_rows(mesh, table)
    rows = -(-n // 4)
    assert placed.shape == (4 * rows, 8) and placed.dtype == data.dtype
    assert [s.data.shape for s in placed.addressable_shards] == [(rows, 8)] * 4
    assert len({s.device for s in placed.addressable_shards}) == 4
    assert max(table.asked) <= rows and sum(table.asked) == n
    assert transfers and max(transfers) == rows * 8 * 4  # never more than one shard
    got = np.asarray(placed)
    np.testing.assert_array_equal(got[:n], data)
    assert not got[n:].any()


def test_feature_rows_replicate_over_dp_and_take_a_memmap(tmp_path, transfers):
    mesh = make_mesh(8, dp=2)
    data = np.arange(1000 * 4, dtype=np.float32).reshape(1000, 4)
    path = tmp_path / "table.bin"
    data.tofile(path)
    placed = shard_feature_rows(mesh, np.memmap(path, np.float32, "r", shape=data.shape))
    assert [s.data.shape for s in placed.addressable_shards] == [(250, 4)] * 8
    assert max(transfers) == 250 * 4 * 4
    np.testing.assert_array_equal(np.asarray(placed), data)


def test_hot_cold_twin_places_what_the_padded_formulation_placed():
    mesh = make_mesh(8, hosts=2)  # (host 2, dp 2, ici 2)
    ici, striped = mesh.shape["ici"], mesh.shape["host"] * mesh.shape["ici"]
    data = np.random.default_rng(2).standard_normal((1003, 6)).astype(np.float32)
    hot, cold = shard_feature_hot_cold(mesh, data, hot_rows=301)
    np.testing.assert_array_equal(np.asarray(hot), pad_to_multiple(data[:301], ici))
    np.testing.assert_array_equal(np.asarray(cold), pad_to_multiple(data[301:], striped))
    assert hot.addressable_shards[0].data.shape == (-(-301 // ici), 6)
    assert cold.addressable_shards[0].data.shape == (-(-702 // striped), 6)


@pytest.mark.parametrize("layout,build", [("flat", build_topology_shards),
                                          ("tiled", build_tiled_topology_shards)])
def test_topology_blocks_go_up_one_shard_at_a_time(layout, build, transfers):
    mesh = make_mesh(4, dp=1)
    topo = graph(3000, 6)
    stopo = shard_topology_rows(mesh, topo, layout=layout)
    first, second, row_start = build(topo.indptr, topo.indices.astype(np.int32), 4)
    assert isinstance(stopo, TiledShardedTopology if layout == "tiled" else ShardedTopology)
    for arr, want in zip(stopo[:2], (first, second)):
        assert arr.shape == want.shape and arr.dtype == jnp.int32
        assert [s.data.shape for s in arr.addressable_shards] == [(1,) + want.shape[1:]] * 4
        np.testing.assert_array_equal(np.asarray(arr), want)
    np.testing.assert_array_equal(np.asarray(stopo.row_start), row_start)
    # the largest single transfer is one block, not the stack of four
    assert max(transfers) == max(first[0].nbytes, second[0].nbytes)
    assert stopo[1].shape[-1] % LANE == 0  # flat blocks are whole lane rows


def test_layout_is_resolved_from_the_graph():
    low, dense = graph(3000, 6), graph(800, 80, seed=3)
    assert tile_slots_per_edge(low.indptr) > TILE_SLOTS_PER_EDGE_MAX
    assert tile_slots_per_edge(dense.indptr) < TILE_SLOTS_PER_EDGE_MAX
    assert resolve_topology_layout(None, low.indptr) == "flat"
    assert resolve_topology_layout(None, dense.indptr) == "tiled"
    # the slots are the tile table's own
    _, tiles, _ = build_tiled_topology_shards(dense.indptr, dense.indices, 1, pad_multiple=1)
    assert tile_slots_per_edge(dense.indptr) == tiles[0].size / dense.indices.shape[0]
    # a named layout is kept whatever the graph; None needs the graph
    assert resolve_topology_layout("tiled", low.indptr) == "tiled"
    assert resolve_topology_layout("flat") == "flat"
    with pytest.raises(ValueError, match="from the graph"):
        resolve_topology_layout(None)
    with pytest.raises(ValueError, match="unsupported"):
        resolve_topology_layout("coo", low.indptr)
    mesh = make_mesh(4, dp=1)
    assert isinstance(shard_topology_rows(mesh, low), ShardedTopology)
    assert isinstance(shard_topology_rows(mesh, dense), TiledShardedTopology)


def test_flat_resolve_reads_what_an_element_gather_reads():
    rng = np.random.default_rng(4)
    indices = jnp.asarray(rng.integers(0, 10**6, 5 * LANE).astype(np.int32))
    ptr = jnp.asarray(np.array([0, 100, 120, 127, 128, 300, 511, 630], np.int32))
    pos = jnp.asarray(rng.integers(0, 140, (8, 5)).astype(np.int32))  # lists straddle rows
    want = jnp.take(indices, jnp.clip(ptr[:, None] + pos, 0, indices.shape[0] - 1))
    np.testing.assert_array_equal(np.asarray(flat_resolve(indices, ptr, pos, 5)),
                                  np.asarray(want))


def _problem(topo, n_dev, dim=12, classes=5, batch=32):
    mesh = make_mesh(n_dev, dp=1)
    n = topo.node_count
    rng = np.random.default_rng(5)
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=len(SIZES), dropout=0.0)
    seeds = rng.choice(n, batch, replace=False).astype(np.int32)
    return mesh, feat, labels, model, seeds


def _sampled(mesh, topo, feat, seeds, key, layout=None):
    sample = make_sharded_topo_sample(mesh, SIZES, pipeline="fused", layout=layout)
    ds, x = sample(key, shard_topology_rows(mesh, topo, layout=layout),
                   shard_feature_rows(mesh, feat), seeds)
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[0], ds._replace(batch_size=None)), \
        np.asarray(x)[0], ds.batch_size


@pytest.mark.parametrize("mean_degree,layout", [(6, "flat"), (80, "tiled")])
def test_sampled_blocks_are_edges_of_the_host_csr_and_rows_of_the_host_table(
        mean_degree, layout):
    topo = graph(1500, mean_degree, seed=6)
    mesh, feat, _, _, seeds = _problem(topo, 4)
    assert resolve_topology_layout(None, topo.indptr) == layout
    ds, x, batch = _sampled(mesh, topo, feat, seeds, jax.random.key(3))
    assert batch == seeds.shape[0] and ds.n_id.shape[0] == 32 * 5 * 4
    np.testing.assert_array_equal(ds.n_id[:32], seeds)
    # gathered rows, bit for bit
    np.testing.assert_array_equal(x.view(np.uint32), feat[ds.n_id].view(np.uint32))
    deg = np.diff(topo.indptr)
    valid = np.ones(32, bool)
    for adj, k in zip(ds.adjs[::-1], SIZES):  # innermost hop first
        w = adj.mask.shape[0]
        assert adj.cols is None and adj.mask.shape == (w, k)
        want = np.where(valid, np.minimum(deg[ds.n_id[:w]], k), 0)
        np.testing.assert_array_equal(adj.mask.sum(axis=1), want)
        for i, j in zip(*np.nonzero(adj.mask)):
            u, v = ds.n_id[i], ds.n_id[w + j * w + i]
            assert v in topo.indices[topo.indptr[u]: topo.indptr[u + 1]]
        valid = np.concatenate([valid, adj.mask.T.reshape(-1)])
    assert int(ds.count) == int(valid.sum())


@pytest.mark.parametrize("mean_degree", [6, 80])
def test_both_layouts_and_one_device_draw_the_same_from_the_same_key(mean_degree):
    topo = graph(1500, mean_degree, seed=7)
    mesh, feat, _, _, seeds = _problem(topo, 4)
    key = jax.random.key(11)
    got = {layout: _sampled(mesh, topo, feat, seeds, key, layout)
           for layout in (None, "flat", "tiled")}
    got["one device"] = _sampled(make_mesh(1), topo, feat, seeds, key)
    want_ds, want_x, _ = got[None]
    for label, (ds, x, _) in got.items():
        np.testing.assert_array_equal(ds.n_id, want_ds.n_id, err_msg=str(label))
        np.testing.assert_array_equal(x, want_x, err_msg=str(label))
        for a, b in zip(ds.adjs, want_ds.adjs):
            np.testing.assert_array_equal(a.mask, b.mask, err_msg=str(label))


def _train(mesh, topo, feat, labels, model, batches, keys, layout=None):
    tx = optax.adam(1e-2)
    step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused",
                                        layout=layout)
    stopo, placed = shard_topology_rows(mesh, topo, layout=layout), shard_feature_rows(mesh, feat)
    x0 = jnp.zeros((batches[0].shape[0] * 5 * 4, feat.shape[1]), jnp.float32)
    sample = make_sharded_topo_sample(mesh, SIZES, pipeline="fused", layout=layout)
    ds0, _ = sample(keys[0], stopo, placed, batches[0])
    adjs0 = jax.tree_util.tree_map(lambda a: a[0], ds0.adjs)
    params = replicate(mesh, model.init(jax.random.key(9), x0, adjs0))
    opt_state = replicate(mesh, tx.init(params))
    labels = replicate(mesh, labels)
    losses = []
    for key, seeds in zip(keys, batches):
        params, opt_state, loss = step(params, opt_state, key, stopo, placed, labels, seeds)
        losses.append(float(loss))
    return losses, jax.tree_util.tree_map(np.asarray, params)


def test_four_shard_step_trains_as_the_one_device_step():
    topo = graph(1500, 6, seed=8)
    mesh, feat, labels, model, _ = _problem(topo, 4)
    rng = np.random.default_rng(12)
    batches = [rng.choice(1500, 32, replace=False).astype(np.int32) for _ in range(3)]
    keys = [jax.random.key(20 + i) for i in range(3)]
    four, params4 = _train(mesh, topo, feat, labels, model, batches, keys)
    one, params1 = _train(make_mesh(1), topo, feat, labels, model, batches, keys)
    tiled, _ = _train(mesh, topo, feat, labels, model, batches, keys, layout="tiled")
    assert four[-1] < four[0]
    np.testing.assert_allclose(four, one, rtol=1e-5)
    np.testing.assert_allclose(four, tiled, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params4), jax.tree_util.tree_leaves(params1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_step_takes_whichever_layout_it_is_handed_and_a_named_one_only():
    topo = graph(600, 6, seed=9)
    mesh, feat, labels, model, seeds = _problem(topo, 4)
    tx = optax.adam(1e-2)
    step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused")
    tiled_only = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused",
                                              layout="tiled")
    placed = shard_feature_rows(mesh, feat)
    x0 = jnp.zeros((32 * 5 * 4, feat.shape[1]), jnp.float32)
    ds0, _ = make_sharded_topo_sample(mesh, SIZES, pipeline="fused")(
        jax.random.key(0), shard_topology_rows(mesh, topo), placed, seeds)
    params = replicate(mesh, model.init(
        jax.random.key(9), x0, jax.tree_util.tree_map(lambda a: a[0], ds0.adjs)))
    args = (params, replicate(mesh, tx.init(params)), jax.random.key(1))
    tail = (placed, replicate(mesh, labels), seeds)
    losses = [float(step(*args, shard_topology_rows(mesh, topo, layout=l), *tail)[2])
              for l in ("flat", "tiled")]
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    assert step._cache_size() == 2  # one jitted step, traced once per stopo type
    assert "module @jit_sharded_topo_train_step " in step.lower(
        *args, shard_topology_rows(mesh, topo), *tail).as_text()
    with pytest.raises(ValueError, match="layout='tiled' but stopo is a ShardedTopology"):
        tiled_only(*args, shard_topology_rows(mesh, topo, layout="flat"), *tail)


def test_placement_spans_and_the_step_counter_record_while_tracing(monkeypatch):
    topo = graph(600, 6, seed=10)
    mesh, feat, _, _, _ = _problem(topo, 4)
    qtrace.trace_report(reset=True)
    shard_feature_rows(mesh, feat)
    total = step_comm_bytes(mesh, SIZES, 32, 12)  # a number of the shapes: records nothing
    qtrace.observe("quiver.step.comm_bytes", total)
    assert qtrace.trace_report() == {}  # off: nothing recorded
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    shard_feature_rows(mesh, feat)
    shard_topology_rows(mesh, topo)
    assert step_comm_bytes(mesh, SIZES, 32, 12) == total
    assert "quiver.step.comm_bytes" not in qtrace.trace_report()
    qtrace.observe("quiver.step.comm_bytes", total)
    report = qtrace.trace_report(reset=True)
    assert report["quiver.shard.features"][0] == 1 and report["quiver.shard.topology"][0] == 1
    model = sampling_comm_bytes(mesh, SIZES, 32, feature_dim=12)
    assert report["quiver.step.comm_bytes"] == (1, total) and total == model["total_bytes"]
    # the rows' all-reduces are most of it: 2 x 3/4 x rows x row bytes
    rows = 32 * 5 * 4
    assert total > 2 * 0.75 * rows * 12 * 4
