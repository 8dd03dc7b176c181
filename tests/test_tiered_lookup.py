"""The tiered padded lookup (PR 32): a table with a host tier answers
`lookup_padded` with the rows the wholly hot table gives, bit for bit, in
one shape whatever the batch's cold count; the build holds no second table
on the host; `TrainPipeline` over it trains the all-hot loop's losses; and
the flat sampler layout, fetched as 128-lane rows, draws what the tiled one
draws."""

import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from qbench import reference
from quiver_tpu import CSRTopo, Feature
from quiver_tpu import feature as feature_mod
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample as sample_ops
from quiver_tpu.pipeline import TrainPipeline, make_tiered_train_step
from quiver_tpu.pyg.sage_sampler import GraphSageSampler
from quiver_tpu.shard_tensor import HostRows

N, DIM = 3000, 24
CFG = {"feat_dim": DIM, "hidden_dim": 16, "classes": 5, "num_layers": 2}


def powerlaw_edges(n=N, e=24000, seed=3):
    rng = np.random.default_rng(seed)
    src = np.minimum((rng.pareto(1.2, e) * 20).astype(np.int64), n - 1)
    return np.stack([src, rng.integers(0, n, e)])


@pytest.fixture(scope="module")
def table():
    return np.random.default_rng(0).standard_normal((N, DIM)).astype(np.float32)


@pytest.fixture(scope="module")
def topo():
    return CSRTopo(edge_index=powerlaw_edges(), num_nodes=N)


def tiered(table, topo, hot_rows, cold_cap=None):
    f = Feature(rank=0, device_list=[0], device_cache_size=hot_rows * table.shape[1] * 4,
                cache_policy="device_replicate", csr_topo=topo)
    f.cold_cap = cold_cap
    f.from_cpu_tensor(table)
    return f


def hot(table):
    f = Feature(rank=0, device_list=[0], device_cache_size=table.nbytes)
    f.from_cpu_tensor(table)
    return f


@pytest.mark.parametrize("hot_rows", [0, 700, 2999])
def test_tiered_lookup_equals_the_wholly_hot_lookup_bit_for_bit(table, topo, hot_rows):
    f, whole = tiered(table, topo, hot_rows, cold_cap=512), hot(table)
    assert f.feature_order is not None and whole.feature_order is None
    rng = np.random.default_rng(1)
    for trial in range(3):
        ids = rng.integers(-50, N + 50, 400).astype(np.int32)  # out of range on both sides
        want = np.asarray(whole.lookup_padded(jnp.asarray(ids)))
        got = np.asarray(f.lookup_padded(jnp.asarray(ids)))
        assert (got.view(np.uint32) == want.view(np.uint32)).all()
        np.testing.assert_array_equal(got, table[np.clip(ids, 0, N - 1)])
    # lanes past `count` ask for nothing and read zero
    got = np.asarray(f.lookup_padded(jnp.asarray(ids), count=250))
    np.testing.assert_array_equal(got[:250], want[:250])
    assert not got[250:].any()


def test_a_batch_over_the_cap_is_counted_and_answered_whole(table, topo, monkeypatch):
    from quiver_tpu import trace

    f = tiered(table, topo, hot_rows=600, cold_cap=64)
    ids = np.arange(N - 300, N, dtype=np.int32)  # the coldest ids: 300 cold rows or so
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace.trace_report(reset=True)
    stage = f.stage_tiered(ids)
    assert stage.n_cold > 64 and f.cold_overflow == 1
    assert stage.cold_rows.shape[0] == -(-stage.n_cold // 64) * 64  # the next multiple
    rep = trace.trace_report(reset=True)
    assert rep["quiver.feature.cold_overflow"] == (1, float(stage.n_cold - 64))
    assert rep["quiver.feature.cold_rows"] == (1, float(stage.n_cold))
    assert rep["quiver.feature.lookup"][0] == rep["quiver.feature.cold_gather"][0] == 1
    np.testing.assert_array_equal(np.asarray(f.lookup_padded(jnp.asarray(ids))), table[ids])
    assert f.cold_overflow == 2
    assert trace.trace_report(reset=True)["quiver.feature.h2d"][0] == 1


def test_one_shape_whatever_the_cold_count(table, topo):
    f = tiered(table, topo, hot_rows=700, cold_cap=256)
    order = f.feature_order
    hottest = np.argsort(order)[:200].astype(np.int32)       # stored rows 0..199: all hot
    coldest = np.argsort(order)[-200:].astype(np.int32)      # all cold
    before = feature_mod._padded_gather_tiered._cache_size()
    shapes = set()
    for ids in (hottest, coldest, np.concatenate([hottest[:100], coldest[:100]])):
        stage = f.stage_tiered(ids)
        shapes.add((stage.mapped.shape, stage.cold_rows.shape))
        np.testing.assert_array_equal(np.asarray(f.lookup_padded(jnp.asarray(ids))), table[ids])
    assert shapes == {((200,), (256, DIM))}
    assert feature_mod._padded_gather_tiered._cache_size() == before + 1
    assert f.cold_overflow == 0


def test_calibrate_cold_cap_takes_the_worst_probe_with_margin_and_granule(table, topo):
    f = tiered(table, topo, hot_rows=700)
    assert f.cold_cap is None
    rng = np.random.default_rng(2)
    probes = [rng.integers(0, N, 500) for _ in range(8)]
    worst = max(int((f.feature_order[p[:400]] >= 700).sum()) for p in probes)
    cap = f.calibrate_cold_cap(probes, counts=[400] * 8, margin=1.1, granule=32)
    assert cap == f.cold_cap == -(-int(worst * 1.1) // 32) * 32 or cap == -(-worst * 1.1 // 32) * 32
    assert worst < cap < worst * 1.1 + 32
    assert f.calibrate_cold_cap(probes, margin=1.5, granule=32, set_cap=False) > cap == f.cold_cap


def test_the_tiered_build_holds_no_second_table_on_the_host(topo):
    big = np.random.default_rng(5).standard_normal((N, 256)).astype(np.float32)  # 3 MB
    jax.block_until_ready(jnp.zeros(8))
    tracemalloc.start()
    f = tiered(big, topo, hot_rows=N // 4)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # the degree order (a few int64 arrays of N) and pieces of the hot prefix:
    # far from the cold tail's 2.3 MB, let alone a whole permuted table
    assert peak < 0.5 * big.nbytes, (peak, big.nbytes)
    hot_table, hot_rows, host = f.tiered_tables()
    assert isinstance(host, HostRows) and host.base is big
    assert hot_rows == N // 4 and hot_table.shape == (N // 4, 256)
    stored = np.argsort(f.feature_order)  # stored row -> caller's row
    np.testing.assert_array_equal(np.asarray(hot_table), big[stored[:hot_rows]])
    np.testing.assert_array_equal(host.rows, stored[hot_rows:])
    np.testing.assert_array_equal(np.asarray(f[np.arange(0, N, 7)]), big[::7])  # eager contract


def test_the_hot_prefix_goes_up_in_pieces(table, topo, monkeypatch):
    from quiver_tpu import shard_tensor

    monkeypatch.setattr(shard_tensor, "PIECE_BYTES", 100 * DIM * 4)  # 100 rows a piece
    f = tiered(table, topo, hot_rows=730)  # 7 whole pieces and a last that starts early
    hot_table, _, _ = f.tiered_tables()
    np.testing.assert_array_equal(np.asarray(hot_table), table[np.argsort(f.feature_order)[:730]])


def train(feature, topo, labels, steps_seeds, keep=None):
    model = GraphSAGE(hidden_dim=CFG["hidden_dim"], out_dim=CFG["classes"],
                      num_layers=CFG["num_layers"], dropout=0.0)
    tx = optax.adam(1e-2)
    sampler = GraphSageSampler(topo, [4, 3], mode="TPU", seed=11, dedup=True,
                               caps=(200, 600), layout="flat")
    st = feature.shard_tensor
    step = make_tiered_train_step(model, tx, labels, st.device_shards[0][1])

    def step_fn(params, opt_state, key, batch):
        out = step(params, opt_state, key, batch)
        if keep is not None:
            keep.append((batch, out[2]))
        return out

    pipe = TrainPipeline(sampler, feature, step_fn, depth=2)
    params = reference.params_of(CFG, 7)
    _, _, losses = pipe.run_epoch(steps_seeds, params, tx.init(params), jax.random.key(0))
    return losses, step.program, pipe


def test_three_pipeline_steps_equal_the_all_hot_loop_and_follow_the_reference(table, topo):
    rng = np.random.default_rng(4)
    labels = rng.integers(0, CFG["classes"], N).astype(np.int32)
    seeds = [rng.integers(0, N, 40) for _ in range(3)]
    f = tiered(table, topo, hot_rows=700, cold_cap=384)
    kept = []
    traces = feature_mod._padded_gather_tiered._cache_size()
    losses, program, pipe = train(f, topo, labels, seeds, keep=kept)
    assert program._cache_size() == 1  # one shape: cold counts differ, the step does not
    cold = [int((np.asarray(b.mapped) >= 700).sum()) for b, _ in kept]
    assert len(set(cold)) > 1 and all(b.cold_rows.shape == (384, DIM) for b, _ in kept)
    assert pipe.stats.cold_rows == sum(cold) and f.cold_overflow == 0
    assert feature_mod._padded_gather_tiered._cache_size() == traces  # the step's own merge
    hot_losses, _, _ = train(hot(table), topo, labels, seeds)
    assert losses == hot_losses  # bit for bit

    def batches():
        for (b, _), s in zip(kept, seeds):
            ids = jnp.clip(b.ds.n_id, 0, N - 1)
            yield (jnp.asarray(table)[ids],
                   [(a.cols, a.mask) for a in b.ds.adjs], jnp.asarray(labels[s]))

    want, _, _ = reference.follow_steps(reference.params_of(CFG, 7), batches(), 1e-2)
    # float32 on both sides on the CPU: the gap is the order of the sums
    np.testing.assert_allclose(losses, want, rtol=2e-6)


def test_flat_layout_fetches_through_lane_rows_and_draws_what_tiled_draws(topo):
    tiled = GraphSageSampler(topo, [5, 4, 3], mode="TPU", seed=5, dedup=True)
    flat = GraphSageSampler(topo, [5, 4, 3], mode="TPU", seed=5, dedup=True, layout="flat")
    windows, rows = flat.lazy_init_quiver()
    # the placed (first edge, degree) table, and no 1-D indptr beside it
    np.testing.assert_array_equal(
        np.asarray(windows), np.stack([topo.indptr[:-1], np.diff(topo.indptr)], axis=1))
    assert rows.ndim == 2 and rows.shape[1] == sample_ops.LANE
    assert rows.shape[0] == -(-topo.edge_count // sample_ops.LANE)
    np.testing.assert_array_equal(np.asarray(rows).reshape(-1)[: topo.edge_count], topo.indices)
    seeds = np.arange(64)
    for _ in range(2):
        a, b = tiled.sample_dense(seeds), flat.sample_dense(seeds)
        np.testing.assert_array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
        assert int(a.count) == int(b.count)
        for x, y in zip(a.adjs, b.adjs):
            np.testing.assert_array_equal(np.asarray(x.cols), np.asarray(y.cols))
            np.testing.assert_array_equal(np.asarray(x.mask), np.asarray(y.mask))
    # the op itself: lane rows, a [E] array of any length, the same neighbours
    ip, ix = topo.to_device()
    assert ix.shape[0] % sample_ops.LANE  # so the [E] form pads inside the program
    cur, valid, key = jnp.arange(200, dtype=ip.dtype), jnp.ones(200, bool), jax.random.key(9)
    n1, v1 = sample_ops.sample_layer(ip, ix, cur, valid, 6, key)
    n2, v2 = sample_ops.sample_layer(windows, rows, cur, valid, 6, key)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(n1)[np.asarray(v1)], np.asarray(n2)[np.asarray(v2)])
    deg = np.diff(topo.indptr)[:200]
    assert (np.asarray(v1).sum(axis=1) == np.minimum(deg, 6)).all()
    text = sample_ops.sample_layer.lower(windows, rows, cur, valid, 6, key).as_text()
    assert f"tensor<{rows.shape[0]}x128x" in text  # gathers read [R, 128], never a 1-D edge array
