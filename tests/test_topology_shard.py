"""Row-sharded graph topology tests — the papers100M axis, hermetic.

The reference scales the graph past device memory with UVA
(quiver_sample.cu:361-421) and proves it only on a real multi-GPU node
(benchmarks/ogbn-papers100M/train_quiver_multi_node.py); here the equivalent
capability — no single device holds the full CSR — is asserted on the fake
8-device mesh, including bit-parity of the collective sample against the
single-chip op.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops.sample import LANE, build_tiled_host, sample_layer, tiled_sample_layer
from quiver_tpu.parallel import (
    make_mesh,
    make_sharded_topo_train_step,
    mesh_axes,
    replicate,
    sampling_comm_bytes,
    shard_feature_rows,
    shard_topology_rows,
    sharded_sample_layer,
    sharded_sample_layer_grouped,
)
from quiver_tpu.parallel.topology import build_topology_shards, partition_rows_by_edges
from quiver_tpu.utils import CSRTopo, shard_map_compat
from test_e2e import make_community_graph


def _powerlaw_graph(n=500, seed=0):
    from quiver_tpu.datasets import synthetic_powerlaw

    edge_index, _, _, _ = synthetic_powerlaw(n, n * 12, seed=seed)
    return CSRTopo(edge_index=edge_index)


def test_partition_reconstructs_csr():
    topo = _powerlaw_graph()
    indptr, indices = np.asarray(topo.indptr), np.asarray(topo.indices)
    for shards in (1, 3, 8):
        wb, xb, rs = build_topology_shards(indptr, indices, shards)
        assert rs[0] == 0 and rs[-1] == indptr.shape[0] - 1
        assert wb.shape[0] == shards and wb.shape[2] == 2
        got_start, got_deg, got_indices, edges_before = [], [], [], 0
        for p in range(shards):
            lo, hi = int(rs[p]), int(rs[p + 1])
            start, deg = wb[p, : hi - lo, 0], wb[p, : hi - lo, 1]
            got_indices.append(xb[p, : int(deg.sum())])
            got_start.append(start + edges_before)
            got_deg.append(deg)
            edges_before += int(deg.sum())
            # padding rows in each window block must read as degree 0
            assert not wb[p, hi - lo :, 1].any()
        np.testing.assert_array_equal(np.concatenate(got_start), indptr[:-1])
        np.testing.assert_array_equal(np.concatenate(got_deg), np.diff(indptr))
        np.testing.assert_array_equal(np.concatenate(got_indices), indices)


def test_partition_edge_balance_on_powerlaw():
    # degree-ordered power-law graphs concentrate edges at low row ids; an
    # equal-ROW split would give shard 0 most of the edges. The edge-balanced
    # split must keep the max block near the mean.
    topo = _powerlaw_graph(n=2000)
    indptr = np.asarray(topo.indptr)
    rs = partition_rows_by_edges(indptr, 8)
    per_shard = np.diff(indptr[rs])
    e = indptr[-1]
    assert per_shard.max() <= e / 8 + indptr.max(initial=0), per_shard
    # and strictly better than the naive equal-row split
    naive = np.diff(indptr[np.linspace(0, indptr.shape[0] - 1, 9).astype(int)])
    assert per_shard.max() <= naive.max()


def test_no_device_holds_full_topology():
    # the capability claim: graph capacity scales with chip count
    topo = _powerlaw_graph(n=2000)
    mesh = make_mesh(8)
    stopo = shard_topology_rows(mesh, topo)
    e = np.asarray(topo.indices).shape[0]
    for shard in stopo.indices.addressable_shards:
        assert shard.data.shape[0] == 1  # one block per device
        assert shard.data.shape[1] < e, (shard.data.shape, e)


def test_sharded_sample_layer_bit_matches_local():
    # owner-exclusive psum assembly + per-row Fisher-Yates means the
    # collective draw is BIT-IDENTICAL to the single-chip op under the same
    # key: deg[b] is what the row's owner sees, and the FY uniforms are
    # row-indexed. Garbage-where-invalid differs (collective zeroes), so
    # compare valid lanes only.
    topo = _powerlaw_graph()
    mesh = make_mesh(8)
    _, feat_axes, _ = mesh_axes(mesh)
    stopo = shard_topology_rows(mesh, topo)
    indptr = jnp.asarray(np.asarray(topo.indptr), jnp.int32)
    indices = jnp.asarray(np.asarray(topo.indices), jnp.int32)
    rng = np.random.default_rng(0)
    cur = jnp.asarray(rng.integers(0, 500, 64), jnp.int32)
    valid_in = jnp.asarray(rng.random(64) < 0.9)
    key = jax.random.key(7)
    k = 6

    ref_nbrs, ref_valid = sample_layer(indptr, indices, cur, valid_in, k, key)

    def f(stopo, cur, valid_in):
        return sharded_sample_layer(
            stopo.windows[0], stopo.indices[0], stopo.row_start,
            cur, valid_in, k, key, feat_axes,
        )

    got_nbrs, got_valid = jax.jit(
        shard_map_compat(
            f, mesh=mesh,
            in_specs=(stopo.specs(feat_axes), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )(stopo, replicate(mesh, cur), replicate(mesh, valid_in))

    np.testing.assert_array_equal(np.asarray(got_valid), np.asarray(ref_valid))
    rv = np.asarray(ref_valid)
    np.testing.assert_array_equal(np.asarray(got_nbrs)[rv], np.asarray(ref_nbrs)[rv])


@pytest.mark.parametrize("pipeline", ["dedup", "fused"])
def test_sharded_topo_train_step_learns(pipeline):
    from quiver_tpu.pyg.sage_sampler import sample_dense_pure

    edge_index, feat_np, labels, n = make_community_graph(per_comm=40)
    topo = CSRTopo(edge_index=edge_index)
    mesh = make_mesh(8)
    stopo = shard_topology_rows(mesh, topo)
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-2)
    step = make_sharded_topo_train_step(mesh, model, tx, sizes=[4, 4], pipeline=pipeline)

    feat = shard_feature_rows(mesh, feat_np)
    labels_d = replicate(mesh, labels.astype(np.int32))
    dp = mesh.shape["dp"]
    batch_global = 8 * dp
    ip = jnp.asarray(topo.indptr.astype(np.int32))
    ix = jnp.asarray(topo.indices.astype(np.int32))
    seeds0 = jnp.arange(batch_global // dp, dtype=jnp.int32)
    ds0 = sample_dense_pure(ip, ix, jax.random.key(0), seeds0, (4, 4))
    if pipeline == "fused":
        from quiver_tpu.pyg.sage_sampler import sample_dense_fused

        ds0 = sample_dense_fused(ip, ix, jax.random.key(0), seeds0, (4, 4))
    x0 = jnp.zeros((ds0.n_id.shape[0], feat_np.shape[1]), jnp.float32)
    params = replicate(mesh, model.init(jax.random.key(1), x0, ds0.adjs))
    opt_state = jax.device_put(tx.init(params), NamedSharding(mesh, P()))

    rng = np.random.default_rng(3)
    losses = []
    for i in range(30):
        seeds = jax.device_put(
            rng.choice(n, batch_global, replace=False).astype(np.int32),
            NamedSharding(mesh, P("dp")),
        )
        params, opt_state, loss = step(
            params, opt_state, jax.random.key(i), stopo, feat, labels_d, seeds
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


@pytest.mark.parametrize("pipeline", ["dedup", "fused"])
def test_multihost_sharded_topo_step(pipeline):
    # (host, dp, ici): topology AND features striped over (host, ici); hosts
    # sample different seeds so the grouped (all_gather over host) sample
    # path runs. Loss must be finite and match shapes; learning is covered
    # by the single-host variant.
    from quiver_tpu.pyg.sage_sampler import sample_dense_fused, sample_dense_pure

    edge_index, feat_np, labels, n = make_community_graph(per_comm=40)
    topo = CSRTopo(edge_index=edge_index)
    mesh = make_mesh(8, hosts=2)
    stopo = shard_topology_rows(mesh, topo)
    # topology must stripe over BOTH host and ici
    assert stopo.windows.sharding.spec[0] == ("host", "ici")
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-2)
    step = make_sharded_topo_train_step(mesh, model, tx, sizes=[4, 4], pipeline=pipeline)

    feat = shard_feature_rows(mesh, feat_np)
    labels_d = replicate(mesh, labels.astype(np.int32))
    _, _, groups = mesh_axes(mesh)
    per_group = 6
    ip = jnp.asarray(topo.indptr.astype(np.int32))
    ix = jnp.asarray(topo.indices.astype(np.int32))
    seeds0 = jnp.arange(per_group, dtype=jnp.int32)
    make0 = sample_dense_fused if pipeline == "fused" else sample_dense_pure
    ds0 = make0(ip, ix, jax.random.key(0), seeds0, (4, 4))
    x0 = jnp.zeros((ds0.n_id.shape[0], feat_np.shape[1]), jnp.float32)
    params = replicate(mesh, model.init(jax.random.key(1), x0, ds0.adjs))
    opt_state = jax.device_put(tx.init(params), NamedSharding(mesh, P()))
    seeds = jax.device_put(
        np.arange(per_group * groups, dtype=np.int32),
        NamedSharding(mesh, P(("host", "dp"))),
    )
    losses = []
    for i in range(3):
        params, opt_state, loss = step(
            params, opt_state, jax.random.key(i), stopo, feat, labels_d, seeds
        )
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses), losses


def test_sampling_comm_bytes_model():
    mesh = make_mesh(8)
    m = sampling_comm_bytes(mesh, (4, 4), batch_per_group=16, feature_dim=32)
    assert m["dcn_bytes"] == 0.0
    assert m["ici_bytes"] > 0
    assert m["total_bytes"] == m["ici_bytes"]
    mesh3 = make_mesh(8, hosts=2)
    m3 = sampling_comm_bytes(mesh3, (4, 4), batch_per_group=16, feature_dim=32)
    assert m3["dcn_bytes"] > 0 and m3["ici_bytes"] > 0
    # no feature gather -> strictly less traffic
    m3b = sampling_comm_bytes(mesh3, (4, 4), batch_per_group=16)
    assert m3b["total_bytes"] < m3["total_bytes"]


# ---------------------------------------------------------------------------
# The contract under test: same PRNG key -> same neighbor ids and valid mask
# as the single-chip samplers (flat CSR and 128-lane tile table), on every
# mesh shape, for lists shorter and longer than a 128-lane row.
# ---------------------------------------------------------------------------


def _graph_with_isolated_rows(n=500, seed=0, mean_degree=12):
    """Power-law graph plus 5 guaranteed degree-0 tail nodes (num_nodes
    overhang), so frontier rows with no neighbors are always exercised."""
    from quiver_tpu.datasets import synthetic_powerlaw

    edge_index, _, _, _ = synthetic_powerlaw(n - 5, (n - 5) * mean_degree, seed=seed)
    return CSRTopo(edge_index=edge_index, num_nodes=n)


def _run_sharded_sample(mesh, stopo, cur, valid_in, k, key):
    """One collective draw through shard_map."""
    _, feat_axes, _ = mesh_axes(mesh)

    def f(stopo, cur, valid_in):
        return sharded_sample_layer(
            stopo.windows[0], stopo.indices[0], stopo.row_start,
            cur, valid_in, k, key, feat_axes,
        )

    return jax.jit(
        shard_map_compat(
            f, mesh=mesh,
            in_specs=(stopo.specs(feat_axes), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )(stopo, replicate(mesh, cur), replicate(mesh, valid_in))


@pytest.mark.parametrize("mean_degree", [6, 80])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_sample_parity(n_shards, mean_degree):
    # sharded == single-chip flat == single-chip tiled, on 2- and 4-shard
    # meshes, with a degree-0 frontier row included; at mean degree 80 lists
    # longer than 128 straddle lane rows of a shard's block
    topo = _graph_with_isolated_rows(mean_degree=mean_degree)
    n = topo.indptr.shape[0] - 1
    mesh = make_mesh(n_shards, dp=1)
    indptr = jnp.asarray(np.asarray(topo.indptr), jnp.int32)
    indices = jnp.asarray(np.asarray(topo.indices), jnp.int32)
    rng = np.random.default_rng(1)
    cur_np = rng.integers(0, n, 64)
    cur_np[:3] = [n - 1, n - 3, n - 5]  # guaranteed degree-0 rows
    deg = np.diff(np.asarray(topo.indptr))
    assert (deg[cur_np[:3]] == 0).all()
    if mean_degree == 80:
        cur_np[3:6] = np.argsort(deg)[-3:]
        assert deg[cur_np[3:6]].min() > LANE
    cur = jnp.asarray(cur_np, jnp.int32)
    valid_in = jnp.asarray(rng.random(64) < 0.9)
    key = jax.random.key(11)
    k = 6

    ref_nbrs, ref_valid = sample_layer(indptr, indices, cur, valid_in, k, key)
    bd, tiles = build_tiled_host(
        np.asarray(topo.indptr), np.asarray(topo.indices), np.int32
    )
    t1_nbrs, t1_valid = tiled_sample_layer(
        jnp.asarray(bd), jnp.asarray(tiles), cur, valid_in, k, key
    )
    flat_n, flat_v = _run_sharded_sample(
        mesh, shard_topology_rows(mesh, topo), cur, valid_in, k, key
    )

    rv = np.asarray(ref_valid)
    assert not rv[:3].any()  # degree-0 frontier rows draw nothing
    for got_v in (t1_valid, flat_v):
        np.testing.assert_array_equal(np.asarray(got_v), rv)
    want = np.asarray(ref_nbrs)[rv]
    for got_n in (t1_nbrs, flat_n):
        np.testing.assert_array_equal(np.asarray(got_n)[rv], want)


def test_sharded_empty_shard_range():
    # one hub row owning ~90% of edges forces empty row ranges at 4 shards;
    # the draw must stay exact through them
    rng = np.random.default_rng(2)
    hub_dst = rng.integers(1, 40, 900)
    tail_src = rng.integers(1, 40, 100)
    tail_dst = rng.integers(1, 40, 100)
    edge_index = np.stack([
        np.concatenate([np.zeros(900, np.int64), tail_src]),
        np.concatenate([hub_dst, tail_dst]),
    ])
    topo = CSRTopo(edge_index=edge_index, num_nodes=40)
    rs = partition_rows_by_edges(np.asarray(topo.indptr), 4)
    assert (np.diff(rs) == 0).any(), rs  # the pathological case is real

    mesh = make_mesh(4, dp=1)
    indptr = jnp.asarray(np.asarray(topo.indptr), jnp.int32)
    indices = jnp.asarray(np.asarray(topo.indices), jnp.int32)
    cur = jnp.asarray(rng.integers(0, 40, 32), jnp.int32)
    valid_in = jnp.ones((32,), bool)
    key = jax.random.key(5)
    k = 4
    ref_nbrs, ref_valid = sample_layer(indptr, indices, cur, valid_in, k, key)
    got_n, got_v = _run_sharded_sample(
        mesh, shard_topology_rows(mesh, topo), cur, valid_in, k, key
    )
    rv = np.asarray(ref_valid)
    np.testing.assert_array_equal(np.asarray(got_v), rv)
    np.testing.assert_array_equal(np.asarray(got_n)[rv], np.asarray(ref_nbrs)[rv])


@pytest.mark.parametrize("mean_degree", [6, 80])
def test_grouped_sample_parity(mean_degree):
    # (host, dp, ici) mesh, hosts carry DISTINCT frontiers: the grouped
    # sampler == the single-chip draw on the host-concatenated frontier
    topo = _graph_with_isolated_rows(mean_degree=mean_degree)
    n = topo.indptr.shape[0] - 1
    mesh = make_mesh(8, hosts=2)
    _, feat_axes, _ = mesh_axes(mesh)
    h = mesh.shape["host"]
    w, k = 24, 5
    rng = np.random.default_rng(3)
    all_cur_np = rng.integers(0, n, h * w)
    all_cur_np[0] = n - 1  # degree-0 row in host 0's frontier
    all_valid_np = rng.random(h * w) < 0.9
    key = jax.random.key(9)

    indptr = jnp.asarray(np.asarray(topo.indptr), jnp.int32)
    indices = jnp.asarray(np.asarray(topo.indices), jnp.int32)
    ref_nbrs, ref_valid = sample_layer(
        indptr, indices, jnp.asarray(all_cur_np, jnp.int32),
        jnp.asarray(all_valid_np), k, key,
    )
    stopo = shard_topology_rows(mesh, topo)

    def f(stopo, cur, valid_in):
        return sharded_sample_layer_grouped(
            stopo.windows[0], stopo.indices[0], stopo.row_start, cur, valid_in,
            k, key, feat_axes, "host",
        )

    by_host = NamedSharding(mesh, P(("host",)))
    got_n, got_v = jax.jit(
        shard_map_compat(
            f, mesh=mesh,
            in_specs=(stopo.specs(feat_axes), P(("host",)), P(("host",))),
            out_specs=(P(("host",), None), P(("host",), None)),
            check_vma=False,
        )
    )(
        stopo,
        jax.device_put(jnp.asarray(all_cur_np, jnp.int32), by_host),
        jax.device_put(jnp.asarray(all_valid_np), by_host),
    )
    rv = np.asarray(ref_valid)
    np.testing.assert_array_equal(np.asarray(got_v), rv)
    np.testing.assert_array_equal(np.asarray(got_n)[rv], np.asarray(ref_nbrs)[rv])
