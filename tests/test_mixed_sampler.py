"""Hybrid CPU+device sampler tests (reference tests/python/cuda/
test_hybrid_sample.py was empty — SURVEY.md 2.5; we do better)."""

import numpy as np
import pytest

from quiver_tpu.utils import CSRTopo
from quiver_tpu.pyg import MixedGraphSageSampler, TrainSampleJob
from conftest import make_random_graph


@pytest.fixture(scope="module")
def graph():
    return CSRTopo(edge_index=make_random_graph(150, 1800, seed=6))


def neighbor_sets(topo):
    return {
        u: set(topo.indices[topo.indptr[u] : topo.indptr[u + 1]].tolist())
        for u in range(topo.node_count)
    }


def test_train_sample_job():
    job = TrainSampleJob(np.arange(50), batch_size=16, seed=0)
    assert len(job) == 4
    sizes = [len(job[i]) for i in range(len(job))]
    assert sizes == [16, 16, 16, 2]
    before = [job[i].copy() for i in range(4)]
    job.shuffle()
    got = np.sort(np.concatenate([job[i] for i in range(4)]))
    np.testing.assert_array_equal(got, np.arange(50))


def test_mode_validation(graph):
    job = TrainSampleJob(np.arange(32), 8)
    with pytest.raises(ValueError):
        MixedGraphSageSampler(job, graph, [4], mode="BAD_MODE")
    # reference spellings accepted
    s = MixedGraphSageSampler(job, graph, [4], num_workers=0, mode="GPU_ONLY")
    assert s.mode == "TPU_ONLY"


def test_mixed_epoch_covers_all_tasks(graph):
    job = TrainSampleJob(np.arange(96), batch_size=16, seed=1)
    sampler = MixedGraphSageSampler(
        job, graph, sizes=[4, 3], num_workers=2, mode="TPU_CPU_MIXED", seed=2
    )
    try:
        nbr = neighbor_sets(graph)
        seen = set()
        for task_idx, ds in sampler:
            seen.add(task_idx)
            n_id = np.asarray(ds.n_id)
            count = int(ds.count)
            assert len(set(n_id[:count].tolist())) == count
            # spot-check edge validity on the innermost hop
            adj = ds.adjs[-1]
            cols, mask = np.asarray(adj.cols), np.asarray(adj.mask)
            for i in range(min(4, cols.shape[0])):
                for j in range(cols.shape[1]):
                    if mask[i, j]:
                        assert int(n_id[cols[i, j]]) in nbr[int(n_id[i])]
        assert seen == set(range(len(job)))
        # second epoch re-splits adaptively using measured times
        n2 = sum(1 for _ in sampler)
        assert n2 == len(job)
        assert sampler.avg_device_time > 0
    finally:
        sampler.shutdown()


def test_cpu_only_mode(graph):
    job = TrainSampleJob(np.arange(32), batch_size=8)
    sampler = MixedGraphSageSampler(
        job, graph, sizes=[3], num_workers=2, mode="CPU_ONLY", seed=3
    )
    try:
        results = dict(iter(sampler))
        assert set(results.keys()) == {0, 1, 2, 3}
    finally:
        sampler.shutdown()


def test_decide_task_num_adapts(graph):
    job = TrainSampleJob(np.arange(64), batch_size=8)
    s = MixedGraphSageSampler(job, graph, [3], num_workers=2)
    # first epoch: even split
    assert s.decide_task_num(8) == 4
    # device much faster -> device takes (nearly) everything
    s.avg_device_time, s.avg_cpu_time = 0.001, 1.0
    assert s.decide_task_num(8) == 8
    # device much slower -> CPU takes (nearly) everything
    s.avg_device_time, s.avg_cpu_time = 1.0, 0.001
    assert s.decide_task_num(8) == 0


def test_split_converges_to_throughput_ratio(graph):
    """VERDICT r2 item 9 'done' criterion: the epoch split must converge to
    the measured throughput ratio device_rate/(device_rate+cpu_rate)."""
    job = TrainSampleJob(np.arange(graph.node_count), batch_size=16, seed=0)
    s = MixedGraphSageSampler(job, graph, sizes=[3, 2], num_workers=2,
                              mode="TPU_CPU_MIXED")
    total = 1000
    # inject measured averages: device 2x faster per task than one worker,
    # but TWO workers -> cpu_rate == device_rate -> 50/50 split
    s.avg_device_time, s.avg_cpu_time = 0.01, 0.02
    assert s.decide_task_num(total) == 500
    # one worker only: device_rate 100/s vs cpu 50/s -> 2/3 device
    s.num_workers = 1
    assert s.decide_task_num(total) == round(total * 100 / 150)
    # slow device: 10/s vs 50/s -> 1/6 device
    s.avg_device_time = 0.1
    assert s.decide_task_num(total) == round(total * 10 / 60)


def test_suggest_num_workers_formula(graph):
    import os

    job = TrainSampleJob(np.arange(graph.node_count), batch_size=16, seed=0)
    s = MixedGraphSageSampler(job, graph, sizes=[3, 2], num_workers=2,
                              mode="TPU_CPU_MIXED")
    # no measurements yet -> keep current
    assert s.suggest_num_workers() == 2
    # cpu task 4x the device task: target 50% share needs 4 workers
    s.avg_device_time, s.avg_cpu_time = 0.01, 0.04
    assert s.suggest_num_workers(0.5, max_workers=32) == 4
    # target 20% device share -> w = 0.04*0.8/(0.2*0.01) = 16
    assert s.suggest_num_workers(0.2, max_workers=32) == 16
    # host core cap applies
    assert s.suggest_num_workers(0.2) <= max(os.cpu_count() or 1, 1)
    # degenerate targets keep current
    assert s.suggest_num_workers(0.0) == s.num_workers


def test_auto_tune_respawns_worker_pool(graph):
    job = TrainSampleJob(np.arange(64), batch_size=16, seed=0)
    s = MixedGraphSageSampler(job, graph, sizes=[3, 2], num_workers=1,
                              mode="TPU_CPU_MIXED", auto_tune_workers=True)
    try:
        # epoch 1: even split, measurements accumulate
        for _ in s:
            pass
        assert s.avg_device_time > 0 and s.avg_cpu_time > 0
        want = s.suggest_num_workers()
        for _ in s:  # epoch 2 retunes at entry
            pass
        assert s.num_workers == want
        # measured split recorded for the stats feedback
        assert s.last_device_share is not None
        assert 0 <= s.last_device_share <= 1
    finally:
        s.shutdown()


def test_pipeline_stats_carry_mixed_measurements(graph):
    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import Feature
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.pipeline import (
        TieredFeaturePipeline,
        TrainPipeline,
        make_tiered_train_step,
    )
    from quiver_tpu.pyg import GraphSageSampler

    n = graph.node_count
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int32)
    f = Feature(rank=0, device_list=[0], device_cache_size="1G")
    f.from_cpu_tensor(feat)
    job = TrainSampleJob(np.arange(64), batch_size=16, seed=0)
    mixed = MixedGraphSageSampler(job, graph, sizes=[3, 2], num_workers=1,
                                  mode="TPU_CPU_MIXED")
    model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-2)
    pipe = TieredFeaturePipeline(f)
    step_fn = make_tiered_train_step(model, tx, jnp.asarray(labels), pipe.hot_table)
    boot = GraphSageSampler(graph, sizes=[3, 2], mode="TPU", seed=1)
    ds0 = boot.sample_dense(np.arange(16))
    x0 = jnp.zeros((ds0.n_id.shape[0], 8), jnp.float32)
    params = model.init(jax.random.key(0), x0, ds0.adjs)
    tp = TrainPipeline(boot, f, step_fn)
    try:
        tp.run_epoch_iter(mixed, params, tx.init(params), jax.random.key(1))
    finally:
        mixed.shutdown()
    assert tp.stats.device_share is not None
    assert tp.stats.avg_device_sample_s > 0
    assert tp.stats.avg_cpu_sample_s > 0


def test_weighted_mixed_epoch(graph):
    """weighted=True flows to BOTH engines: the device sampler and the
    spawned CPU workers (per-edge weights shared via shm, native weighted
    k-subset). Zero-weight edges never appear from either side."""
    from quiver_tpu.ops.cpu_kernels import native_available

    if not native_available():
        pytest.skip("native engine not built")
    n = graph.node_count
    # only even-id destinations carry weight
    ew = np.where(np.asarray(graph.indices) % 2 == 0, 1.0, 0.0).astype(np.float32)
    topo = CSRTopo(indptr=graph.indptr, indices=graph.indices, edge_weights=ew)
    job = TrainSampleJob(np.arange(n), batch_size=25, seed=0)
    # CPU_ONLY forces every task through the spawned weighted workers —
    # a mixed split could route them all to the device sampler and leave
    # the worker path untested
    s = MixedGraphSageSampler(
        job, topo, sizes=[4], num_workers=1, mode="CPU_ONLY",
        weighted=True,
    )
    try:
        seen_tasks = set()
        for task_idx, ds in s:
            seen_tasks.add(task_idx)
            b = ds.batch_size
            sampled = np.asarray(ds.n_id)[b : int(ds.count)]
            assert (sampled % 2 == 0).all(), sampled[:10]
    finally:
        s.shutdown()
    assert seen_tasks == set(range(len(job)))
    assert s.avg_cpu_time > 0  # the workers really did the drawing
    # misconfiguration fails loudly
    with pytest.raises(ValueError, match="edge_weights"):
        MixedGraphSageSampler(job, graph, sizes=[4], weighted=True)


def test_weighted_mixed_max_deg_guard(graph):
    """In weighted MIXED mode the device engine weights only each row's
    first ``max_deg`` edges while CPU workers weight all of them — a graph
    whose max degree exceeds max_deg would mix two distributions in one
    epoch, so construction must refuse. max_deg is also forwarded to the
    device sampler (it was previously stuck at the 512 default)."""
    ew = np.ones(len(graph.indices), np.float32)
    topo = CSRTopo(indptr=graph.indptr, indices=graph.indices, edge_weights=ew)
    job = TrainSampleJob(np.arange(32), 8)
    max_deg_graph = int(np.max(np.diff(np.asarray(topo.indptr))))
    with pytest.raises(ValueError, match="max_deg"):
        MixedGraphSageSampler(
            job, topo, sizes=[4], num_workers=1, mode="TPU_CPU_MIXED",
            weighted=True, max_deg=max_deg_graph - 1,
        )
    # HOST_CPU_MIXED is exempt: its "device" half is the host native
    # engine, which (like the CPU workers) weights ALL edges — no window
    from quiver_tpu.ops.cpu_kernels import native_available

    if native_available():
        sh = MixedGraphSageSampler(
            job, topo, sizes=[4], num_workers=1, mode="HOST_CPU_MIXED",
            weighted=True, max_deg=max_deg_graph - 1,
        )
        sh.shutdown()
    # with no CPU half there is no second distribution: num_workers=0
    # stays device-only and must NOT be rejected
    s = MixedGraphSageSampler(
        job, topo, sizes=[4], num_workers=0, mode="TPU_CPU_MIXED",
        weighted=True, max_deg=max_deg_graph - 1,
    )
    assert s.device_sampler.max_deg == max_deg_graph - 1
    # a sufficient max_deg constructs and reaches the device sampler
    s2 = MixedGraphSageSampler(
        job, topo, sizes=[4], num_workers=0, mode="TPU_CPU_MIXED",
        weighted=True, max_deg=max_deg_graph,
    )
    assert s2.device_sampler.max_deg == max_deg_graph


def test_worker_death_recovery(graph):
    """Failure recovery beyond the reference (which hangs its epoch if a
    worker dies with a task in flight): killing one of two workers
    mid-epoch resubmits pending tasks to the survivor and the epoch still
    yields every task exactly once."""
    n = graph.node_count
    job = TrainSampleJob(np.arange(n), batch_size=10, seed=0)  # many tasks
    s = MixedGraphSageSampler(
        job, graph, sizes=[4], num_workers=2, mode="CPU_ONLY"
    )
    try:
        seen = []
        it = iter(s)
        seen.append(next(it)[0])
        # one worker dies with the queue still loaded
        s._workers[0].terminate()
        s._workers[0].join(timeout=10)
        for task_idx, ds in it:
            seen.append(task_idx)
    finally:
        s.shutdown()
    assert sorted(seen) == list(range(len(job))), seen


def test_all_workers_dead_fails_fast_and_heals_next_epoch(graph):
    """Whole pool dead MID-epoch -> RuntimeError naming the cause within
    seconds, not a 120 s stall. The NEXT epoch heals: lazy_init respawns
    dead workers, so a bad epoch doesn't poison the sampler forever."""
    import time as time_mod

    job = TrainSampleJob(np.arange(40), batch_size=10, seed=0)
    s = MixedGraphSageSampler(job, graph, sizes=[4], num_workers=1, mode="CPU_ONLY")
    try:
        s.lazy_init()
        first = s._workers[0]
        first.terminate()
        first.join(timeout=10)
        # lazy_init at __iter__ heals the pool; kill again right after the
        # submit happened by patching lazy_init to kill post-heal
        orig_lazy = s.lazy_init

        def killing_lazy():
            orig_lazy()
            for p in s._workers:
                p.terminate()
                p.join(timeout=10)

        s.lazy_init = killing_lazy
        t0 = time_mod.monotonic()
        with pytest.raises(RuntimeError, match="workers died"):
            for _ in s:
                pass
        assert time_mod.monotonic() - t0 < 30  # fast, not the 120 s stall
        # healing: restore lazy_init, next epoch respawns and completes
        s.lazy_init = orig_lazy
        seen = sorted(t for t, _ in s)
        assert seen == list(range(len(job)))
    finally:
        s.shutdown()


def test_worker_imports_touch_no_device():
    """The spawned CPU workers import this package (and chip_smoke.py as
    their parent's main module) while the parent holds the chip, and a chip
    belongs to one process: nothing at import time may initialise a JAX
    backend."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import quiver_tpu.pyg.mixed_sampler, chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, PYTHONPATH=root),
    )
    assert out.returncode == 0, out.stderr[-2000:]
