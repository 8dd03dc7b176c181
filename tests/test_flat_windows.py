"""The flat layout's window table (PR 33): the ``[N, 2]`` (first edge,
degree) table is built once on the host and PLACED with the lane rows, the
one-hop op takes it as it is, and no program of a flat sampler builds an
``[N]``-sized array again. A 1-D ``indptr`` still works and still stacks
inside the program: the same neighbours from the same key either way."""

import functools
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

from quiver_tpu import CSRTopo
from quiver_tpu.ops import sample as sample_ops
from quiver_tpu.pyg.sage_sampler import GraphSageSampler, sample_dense_program

N = 3001  # a prime: no other dimension of a sampler's program equals it


def powerlaw_topo(n=N, e=24000, seed=3):
    rng = np.random.default_rng(seed)
    src = np.minimum((rng.pareto(1.2, e) * 20).astype(np.int64), n - 1)
    return CSRTopo(edge_index=np.stack([src, rng.integers(0, n, e)]), num_nodes=n)


@pytest.fixture(scope="module")
def topo():
    return powerlaw_topo()


def test_the_host_builds_first_edge_and_degree_and_pads_with_degree_zero(topo):
    table = sample_ops.flat_windows_host(topo.indptr, np.int32)
    assert table.shape == (N, 2) and table.dtype == np.int32
    np.testing.assert_array_equal(table[:, 0], topo.indptr[:-1])
    np.testing.assert_array_equal(table[:, 1], topo.degree)
    padded = sample_ops.flat_windows_host(topo.indptr, np.int64, rows=N + 5)
    assert padded.shape == (N + 5, 2) and padded.dtype == np.int64
    np.testing.assert_array_equal(padded[:N], table)
    np.testing.assert_array_equal(padded[N:], [[topo.edge_count, 0]] * 5)
    empty = sample_ops.flat_windows_host(np.zeros(1, np.int64), np.int32, rows=3)
    np.testing.assert_array_equal(empty, np.zeros((3, 2), np.int32))


@pytest.mark.parametrize("first,second", [("windows", "rows"), ("windows", "edges"),
                                          ("indptr", "rows")])
def test_sample_layer_draws_the_same_from_every_form_of_the_flat_graph(topo, first, second):
    """The placed pair, the 1-D arrays and either mix: same key, same
    neighbours and validity, invalid and out-of-range seeds included."""
    indptr, edges = topo.to_device()
    windows, rows = topo.to_device_lane_rows()
    assert windows.shape == (N, 2) and windows.dtype == indptr.dtype
    assert edges.shape[0] % sample_ops.LANE  # so the [E] form pads inside the program
    rng = np.random.default_rng(1)
    seeds = jnp.asarray(np.concatenate([
        np.arange(64), rng.integers(0, N, 180), [N - 1, N, N + 7, -3]]).astype(np.int32))
    seed_valid = jnp.asarray(rng.random(seeds.shape[0]) < 0.9)
    key, k = jax.random.key(9), 6
    want_n, want_v = sample_ops.sample_layer(indptr, edges, seeds, seed_valid, k, key)
    got_n, got_v = sample_ops.sample_layer(
        {"windows": windows, "indptr": indptr}[first],
        {"rows": rows, "edges": edges}[second], seeds, seed_valid, k, key)
    want_v = np.asarray(want_v)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    np.testing.assert_array_equal(np.asarray(got_n)[want_v], np.asarray(want_n)[want_v])
    # and they are the host's neighbours, min(degree, k) of them a valid seed
    clipped = np.clip(np.asarray(seeds), 0, N - 1)
    want_count = np.where(np.asarray(seed_valid), np.minimum(topo.degree[clipped], k), 0)
    np.testing.assert_array_equal(want_v.sum(axis=1), want_count)
    for u, row, ok in zip(clipped, np.asarray(got_n), want_v):
        assert set(row[ok]) <= set(topo.indices[topo.indptr[u]: topo.indptr[u + 1]])


def test_row_windows_reads_a_placed_table_and_a_1d_indptr_alike(topo):
    indptr, _ = topo.to_device()
    windows, _ = topo.to_device_lane_rows()
    seeds = jnp.asarray(np.array([0, 5, N - 1, N + 3, -1, 17], np.int32))
    valid = jnp.asarray(np.array([1, 1, 1, 1, 1, 0], bool))
    base_t, deg_t = sample_ops.row_windows(windows, seeds, valid)
    base_p, deg_p = sample_ops.row_windows(indptr, seeds, valid)
    np.testing.assert_array_equal(np.asarray(base_t), np.asarray(base_p))
    np.testing.assert_array_equal(np.asarray(deg_t), np.asarray(deg_p))
    clipped = np.clip(np.asarray(seeds), 0, N - 1)
    np.testing.assert_array_equal(np.asarray(base_t), topo.indptr[clipped])
    np.testing.assert_array_equal(np.asarray(deg_t), np.where(valid, topo.degree[clipped], 0))
    assert deg_t.dtype == jnp.int32


def _results_with_a_dim(jaxpr, dims):
    """Every equation RESULT, sub-programs included, one of whose dimensions
    is in ``dims``: ``[(primitive, shape)]``."""
    found = []
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                  if set(getattr(v.aval, "shape", ())) & dims]
        for sub in jax_core.jaxprs_in_params(eqn.params):
            found += _results_with_a_dim(sub, dims)
    return found


def _program_jaxpr(sampler, dedup):
    graph, _, id_dtype = sampler._graph_and_bind()
    program = functools.partial(
        sample_dense_program, sizes=sampler.sizes, caps=sampler.caps if dedup else None,
        dedup=dedup, hop=sampler._hop())
    seeds = jnp.arange(64, dtype=id_dtype)
    return jax.make_jaxpr(program)(sampler._key0, np.uint32(0), seeds, graph).jaxpr, graph


@pytest.mark.parametrize("dedup", [True, False], ids=["dedup", "fused"])
def test_a_flat_samplers_program_builds_nothing_of_the_node_counts_size(topo, dedup):
    """`sample_dense_program` over the placed pair: the ``[N, 2]`` table is
    an argument and no equation, at any depth, has a result with a dimension
    of N or N + 1 (no slice, concatenate, pad or dynamic-update-slice of the
    node count): the stack cannot come back into the launch unseen."""
    sampler = GraphSageSampler(topo, [5, 4, 3], mode="TPU", seed=5, dedup=dedup,
                               caps=(400, 1200, 2600) if dedup else None, layout="flat")
    jaxpr, graph = _program_jaxpr(sampler, dedup)
    assert [tuple(a.shape) for a in graph] == [(N, 2), (-(-topo.edge_count // 128), 128)]
    assert (N, 2) in [tuple(v.aval.shape) for v in jaxpr.invars]
    assert _results_with_a_dim(jaxpr, {N, N + 1}) == []
    # the tiled layout's program never had one either
    tiled = GraphSageSampler(topo, [5, 4, 3], mode="TPU", seed=5, dedup=dedup,
                             caps=(400, 1200, 2600) if dedup else None)
    assert _results_with_a_dim(_program_jaxpr(tiled, dedup)[0], {N, N + 1}) == []


def test_the_walk_sees_the_stack_where_a_1d_indptr_is_handed_over(topo):
    """The structural test's own control: the one-hop op over a 1-D
    ``indptr`` does stack ``[N]``-sized results in the program (what the
    weighted flat sampler still does), and the walk finds them."""
    indptr, edges = topo.to_device()
    seeds, valid = jnp.arange(64, dtype=jnp.int32), jnp.ones(64, bool)
    hop = functools.partial(sample_ops.sample_layer, k=4, key=jax.random.key(0))
    stacked = _results_with_a_dim(jax.make_jaxpr(hop)(indptr, edges, seeds, valid).jaxpr, {N})
    assert {shape for _, shape in stacked} >= {(N,), (N, 2)}
    windows, rows = topo.to_device_lane_rows()
    assert _results_with_a_dim(jax.make_jaxpr(hop)(windows, rows, seeds, valid).jaxpr, {N}) == []


def test_the_placed_pair_is_cached_dropped_by_pickle_and_placed_again():
    topo = powerlaw_topo(seed=4)
    with pytest.raises(ValueError, match="no device layout"):
        topo.drop_host_edges()
    sampler = GraphSageSampler(topo, [3, 2], mode="TPU", layout="flat", seed=1)
    placed = sampler.lazy_init_quiver()
    device = sampler._device_obj()
    assert topo.to_device_lane_rows(device) is placed and topo._lanes_cache[1] is placed
    windows, rows = placed
    assert windows.shape == (N, 2) and rows.shape == (-(-topo.edge_count // 128), 128)
    clone = pickle.loads(pickle.dumps(topo))
    assert clone._lanes_cache is None and topo._lanes_cache[1] is placed
    again_windows, again_rows = clone.to_device_lane_rows(device)
    np.testing.assert_array_equal(np.asarray(again_windows), np.asarray(windows))
    np.testing.assert_array_equal(np.asarray(again_rows), np.asarray(rows))
    # the placed layout lets the host's edges go; the cached pair stays
    topo.drop_host_edges()
    assert topo.indices is None and topo.to_device_lane_rows(device) is placed
    assert sampler.lazy_init_quiver() is placed
    assert int(sampler.sample_dense(np.arange(16)).count) > 16
