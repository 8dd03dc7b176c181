"""Placement shard by shard and what a sharded step sampled (ISSUE 28):
`shard_feature_rows` / `shard_topology_rows` never hand a device more than
its own block and never build the stack on the host; four shards draw what
one device draws from the same key, for a low-degree graph and a
products-like one; `make_sharded_topo_sample` returns the samples and rows
the train step trained on, held here against the host CSR, the host table
and the one-device step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from quiver_tpu import CSRTopo, trace as qtrace
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops.sample import LANE, flat_resolve, sample_layer
from quiver_tpu.parallel import (
    ShardedTopology,
    make_mesh,
    make_sharded_topo_sample,
    make_sharded_topo_train_step,
    mesh_axes,
    pad_to_multiple,
    replicate,
    sampling_comm_bytes,
    shard_feature_hot_cold,
    shard_feature_rows,
    shard_topology_rows,
    sharded_sample_layer,
    step_comm_bytes,
)
from quiver_tpu.parallel.topology import build_topology_shards
from quiver_tpu.utils import shard_map_compat

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


def test_topology_blocks_go_up_one_shard_at_a_time(transfers):
    mesh = make_mesh(4, dp=1)
    topo = graph(3000, 6)
    stopo = shard_topology_rows(mesh, topo)
    win, idx, row_start = build_topology_shards(topo.indptr, topo.indices.astype(np.int32), 4)
    assert win.ndim == 3 and win.shape[2] == 2 and win.shape[1] % (8 * LANE) == 0
    assert isinstance(stopo, ShardedTopology)
    for arr, want in zip(stopo[:2], (win, idx)):
        assert arr.shape == want.shape and arr.dtype == jnp.int32
        assert [s.data.shape for s in arr.addressable_shards] == [(1,) + want.shape[1:]] * 4
        np.testing.assert_array_equal(np.asarray(arr), want)
    np.testing.assert_array_equal(np.asarray(stopo.row_start), row_start)
    # the largest single transfer is one block, not the stack of four
    assert max(transfers) == max(win[0].nbytes, idx[0].nbytes)
    assert stopo.indices.shape[-1] % LANE == 0  # blocks are whole lane rows


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


def _sampled(mesh, topo, feat, seeds, key):
    sample = make_sharded_topo_sample(mesh, SIZES, pipeline="fused")
    ds, x = sample(key, shard_topology_rows(mesh, topo),
                   shard_feature_rows(mesh, feat), seeds)
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[0], ds._replace(batch_size=None)), \
        np.asarray(x)[0], ds.batch_size


@pytest.mark.parametrize("mean_degree", [6, 80])
def test_sampled_blocks_are_edges_of_the_host_csr_and_rows_of_the_host_table(mean_degree):
    topo = graph(1500, mean_degree, seed=6)
    mesh, feat, _, _, seeds = _problem(topo, 4)
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
def test_four_shards_and_one_device_draw_the_same_from_the_same_key(mean_degree):
    topo = graph(1500, mean_degree, seed=7)
    mesh, feat, _, _, seeds = _problem(topo, 4)
    key = jax.random.key(11)
    ds, x, _ = _sampled(mesh, topo, feat, seeds, key)
    want_ds, want_x, _ = _sampled(make_mesh(1), topo, feat, seeds, key)
    np.testing.assert_array_equal(ds.n_id, want_ds.n_id)
    np.testing.assert_array_equal(x, want_x)
    for a, b in zip(ds.adjs, want_ds.adjs):
        np.testing.assert_array_equal(a.mask, b.mask)


@pytest.mark.parametrize("mean_degree", [6, 80])
def test_the_hop_over_placed_window_blocks_draws_what_the_one_device_hop_draws(mean_degree):
    """`sharded_sample_layer` over the ``[P, R_max, 2]`` window blocks that
    `shard_topology_rows` places, on four devices, against `sample_layer`
    over the pair `CSRTopo.to_device_lane_rows` places, same key: the same
    neighbours and validity (the collective zeroes what is not valid)."""
    topo = graph(1500, mean_degree, seed=13)
    mesh = make_mesh(4, dp=1)
    _, feat_axes, _ = mesh_axes(mesh)
    stopo = shard_topology_rows(mesh, topo)
    r_max = stopo.windows.shape[1]
    assert stopo.windows.shape == (4, r_max, 2) and r_max % (8 * LANE) == 0
    assert [s.data.shape for s in stopo.windows.addressable_shards] == [(1, r_max, 2)] * 4
    specs = stopo.specs(feat_axes)
    assert specs.windows == P(feat_axes, None, None) and specs.indices == P(feat_axes, None)
    # a shard's block: its rows' (first LOCAL edge, degree), then degree 0
    row_start, blocks = np.asarray(stopo.row_start), np.asarray(stopo.windows)
    for p in range(4):
        lo, hi = row_start[p], row_start[p + 1]
        np.testing.assert_array_equal(blocks[p, : hi - lo, 0], topo.indptr[lo:hi] - topo.indptr[lo])
        np.testing.assert_array_equal(blocks[p, : hi - lo, 1], topo.degree[lo:hi])
        assert not blocks[p, hi - lo:, 1].any()

    rng = np.random.default_rng(2)
    cur = jnp.asarray(np.concatenate([rng.integers(0, 1500, 252), [0, 1499, 1500, 1507]])
                      .astype(np.int32))
    cur_valid = jnp.asarray(rng.random(256) < 0.9)
    key, k = jax.random.key(17), 5
    windows, rows = topo.to_device_lane_rows()
    want_n, want_v = sample_layer(windows, rows, cur, cur_valid, k, key)

    def hop(stopo, cur, cur_valid):
        blocks = (b.reshape(b.shape[1:]) for b in (stopo.windows, stopo.indices))
        return sharded_sample_layer(*blocks, stopo.row_start, cur, cur_valid, k, key, feat_axes)

    got_n, got_v = jax.jit(shard_map_compat(
        hop, mesh=mesh, in_specs=(specs, P(), P()), out_specs=(P(), P()), check_vma=False,
    ))(stopo, replicate(mesh, cur), replicate(mesh, cur_valid))
    want_v = np.asarray(want_v)
    # a seed past the last node is nobody's row: the one-device hop clips it
    # to the last node, the collective draws nothing for it
    owned = np.asarray(cur) < 1500
    np.testing.assert_array_equal(np.asarray(got_v)[owned], want_v[owned])
    assert not np.asarray(got_v)[~owned].any()
    keep = want_v & owned[:, None]
    np.testing.assert_array_equal(np.asarray(got_n)[keep], np.asarray(want_n)[keep])
    assert not np.asarray(got_n)[~np.asarray(got_v)].any()


def _train(mesh, topo, feat, labels, model, batches, keys):
    tx = optax.adam(1e-2)
    step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused")
    stopo, placed = shard_topology_rows(mesh, topo), shard_feature_rows(mesh, feat)
    x0 = jnp.zeros((batches[0].shape[0] * 5 * 4, feat.shape[1]), jnp.float32)
    sample = make_sharded_topo_sample(mesh, SIZES, pipeline="fused")
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
    assert four[-1] < four[0]
    np.testing.assert_allclose(four, one, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params4), jax.tree_util.tree_leaves(params1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_layout_keyword_takes_none_and_flat_and_refuses_the_rest():
    """ROADMAP D13: the benchmark still passes ``layout=None`` to all three."""
    topo = graph(600, 6, seed=9)
    mesh, feat, labels, model, seeds = _problem(topo, 4)
    tx = optax.adam(1e-2)
    placed = shard_feature_rows(mesh, feat)
    x0 = jnp.zeros((32 * 5 * 4, feat.shape[1]), jnp.float32)
    losses = []
    for layout in (None, "flat"):
        stopo = shard_topology_rows(mesh, topo, layout=layout)
        assert isinstance(stopo, ShardedTopology)
        ds0, _ = make_sharded_topo_sample(mesh, SIZES, pipeline="fused", layout=layout)(
            jax.random.key(0), stopo, placed, seeds)
        params = replicate(mesh, model.init(
            jax.random.key(9), x0, jax.tree_util.tree_map(lambda a: a[0], ds0.adjs)))
        step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused",
                                            layout=layout)
        args = (params, replicate(mesh, tx.init(params)), jax.random.key(1), stopo,
                placed, replicate(mesh, labels), seeds)
        losses.append(float(step(*args)[2]))
        assert "module @jit_sharded_topo_train_step " in step.lower(*args).as_text()
    assert losses[0] == losses[1]
    for layout in ("tiled", "coo"):
        for build in (
            lambda: shard_topology_rows(mesh, topo, layout=layout),
            lambda: make_sharded_topo_sample(mesh, SIZES, pipeline="fused", layout=layout),
            lambda: make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused",
                                                 layout=layout),
        ):
            with pytest.raises(ValueError, match="unsupported topology layout"):
                build()


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


def test_the_four_chip_cells_comm_bytes_and_the_models_keys():
    """What the ledger's ``comm_bytes_per_step`` reads in
    papers100M-sage.train-sharded4: the cell's shapes, to the byte."""
    mesh = make_mesh(4, dp=1)
    assert step_comm_bytes(mesh, (15, 10, 5), 1024, 128) == 843_436_032.0
    model = sampling_comm_bytes(mesh, (15, 10, 5), 1024, feature_dim=128)
    assert set(model) == {"ici_bytes", "dcn_bytes", "total_bytes"}
    assert model["dcn_bytes"] == 0.0 and model["total_bytes"] == 843_436_032.0


def test_every_exported_name_of_parallel_is_there():
    import quiver_tpu.parallel as parallel

    assert len(set(parallel.__all__)) == len(parallel.__all__)
    assert [n for n in parallel.__all__ if not hasattr(parallel, n)] == []
