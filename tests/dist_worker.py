"""Worker for the 2-process hermetic exchange test (run via subprocess).

Each process is one "host" of a 2-host pod: it initializes jax.distributed
over a local coordinator, holds ONLY its own feature block, and runs the
collective exchange. Proves the multi-process path (per-process shards via
jax.make_array_from_process_local_data) without a real pod — the reference
could only test its NcclComm against live LAN IPs (test_comm.py:9-11).

usage: python dist_worker.py <process_id> <coordinator_port> [mode]

mode "exchange" (default): TpuComm exchange + DistFeature lookups.
mode "train": ONE `make_sharded_train_step` step on the process-spanning
(dp=1, ici=2) mesh — the loss is printed so the parent test can assert it
matches a single-controller run of the identical step (same keys, same
mesh shape, same arithmetic; only the process layout differs).
mode "train_topo": same, through `make_sharded_topo_train_step` with the
row-sharded topology (`ShardedTopology`): each process ends up holding
only its own block of the CSR.
mode "serve": the serve-shaped exchange (`TpuComm.exchange_serve`) across
two REAL processes: each holds only its own seed-ownership shard
(topology closure + owned feature rows), runs a local pipelined
`ServeEngine` as the registered answerer, and routes a mixed-ownership
request batch through the collective — seed ids out, logits back. Each
worker verifies the REMOTE rows it got back bit-match a local simulation
of the peer's engine (deterministic build + key stream), i.e. the
cross-host hop added nothing numerically.
"""

import os
import sys


def train_main(pid: int, port: str, topo: bool = False) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "")

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2 and jax.device_count() == 2

    import numpy as np

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))  # repo root (quiver_tpu, entry)
    sys.path.insert(0, here)  # tests dir (sharded_train_case)
    from sharded_train_case import CASE_SEEDS, build_case

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    case = build_case()
    mesh = case["make_mesh"]()

    def gput(x, spec):
        """Global array from identical per-process host data — the
        multi-controller placement primitive (device_put with a
        process-spanning sharding is version-sensitive; the callback form
        is not)."""
        x = np.asarray(x)
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    params = jax.tree_util.tree_map(lambda a: gput(a, P()), case["params_np"])
    opt_state = jax.tree_util.tree_map(lambda a: gput(a, P()), case["opt_np"])
    if topo:
        from quiver_tpu.parallel import ShardedTopology

        win_b, idx_b, row_start = case["stopo_np"]
        stopo = ShardedTopology(
            windows=gput(win_b, P(("ici",), None, None)),
            indices=gput(idx_b, P(("ici",), None)),
            row_start=gput(row_start, P()),
        )
        step = case["make_step_topo"](mesh)
        args = (
            params, opt_state, jax.random.key(2), stopo,
            gput(case["feat_padded"], P(("ici",), None)),
            gput(case["labels"], P()),
            gput(CASE_SEEDS, P("dp")),
        )
    else:
        step = case["make_step"](mesh)
        args = (
            params, opt_state, jax.random.key(2),
            gput(case["indptr"], P()), gput(case["indices"], P()),
            gput(case["feat_padded"], P(("ici",), None)),
            gput(case["labels"], P()),
            gput(CASE_SEEDS, P("dp")),
        )
    _, _, loss = step(*args)
    print(f"worker {pid} loss {float(loss):.8f}", flush=True)
    print(f"worker {pid} OK", flush=True)


def serve_main(pid: int, port: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "")

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2 and jax.device_count() == 2

    import numpy as np

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from quiver_tpu import CSRTopo
    from quiver_tpu.comm import TpuComm
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.pyg.sage_sampler import GraphSageSampler
    from quiver_tpu.serve import ServeConfig, ServeEngine, shard_topology_by_owner

    # deterministic 2-community graph: the community partition is k-hop
    # CLOSED, so each host's topology closure is exactly its own community
    # (true 1/H shards) and its owned feature rows cover every sampled id
    rng = np.random.default_rng(7)
    per, intra, dim, sizes, seed = 40, 6, 8, [4, 4], 5
    n = 2 * per
    src, dst = [], []
    for u in range(n):
        cu = u // per
        for v in rng.choice(per, intra, replace=False) + cu * per:
            src.append(u)
            dst.append(int(v))
    edge_index = np.stack([np.array(src), np.array(dst)])
    feat_full = np.random.default_rng(8).standard_normal((n, dim)).astype(np.float32)
    global2host = (np.arange(n) // per).astype(np.int32)
    model = GraphSAGE(hidden_dim=16, out_dim=6, num_layers=2, dropout=0.0)
    topo = CSRTopo(edge_index=edge_index)

    def build_engine(host):
        """Any host's engine is deterministically reconstructible (same
        shard build, same sampler seed) — workers use that to VERIFY the
        peer's answers without ever serving from its state."""
        shard_topo, st = shard_topology_by_owner(
            topo, global2host, host, hops=len(sizes) - 1
        )
        assert st["edges_kept"] * 2 == st["edges_total"], st  # true 1/H shard
        feat = np.zeros_like(feat_full)
        owned = np.nonzero(global2host == host)[0]
        feat[owned] = feat_full[owned]  # this host's rows only
        sampler = GraphSageSampler(shard_topo, sizes=sizes, mode="TPU", seed=seed)
        return ServeEngine(
            model, params, sampler, feat,
            ServeConfig(max_batch=16, max_delay_ms=1e9, record_dispatches=True),
        )

    s0 = GraphSageSampler(topo, sizes=sizes, mode="TPU", seed=seed)
    ds0 = s0.sample_dense(np.arange(8, dtype=np.int64))
    params = model.init(
        jax.random.key(0), jnp.zeros((ds0.n_id.shape[0], dim)), ds0.adjs
    )

    engine = build_engine(pid)
    mesh = Mesh(np.array(jax.devices()), ("host",))
    comm = TpuComm(rank=pid, world_size=2, mesh=mesh)
    comm.static_budget = 8
    out_dim = 6

    def answerer(recv_ids):
        out = np.zeros((2, comm.static_budget, out_dim), np.float32)
        for req in range(2):
            valid = recv_ids[req] >= 0
            if valid.any():
                ids = recv_ids[req][valid].astype(np.int64)
                out[req, valid] = np.asarray(engine.predict(ids))
        return out

    comm.register_serve_answerer(pid, answerer)

    # each worker's (deterministic) mixed-ownership request batch, split by
    # owner — both workers know BOTH traces, so each can simulate the
    # peer's full received batch when verifying
    traces = {
        0: np.array([3, per + 5, 7, per + 9], np.int64),
        1: np.array([per + 1, 2, per + 11, 6], np.int64),
    }
    host2ids = [traces[pid][global2host[traces[pid]] == h] for h in range(2)]
    res = comm.exchange_serve(host2ids, out_dim=out_dim)

    # loopback rows == the local engine's own results
    own = host2ids[pid]
    if own.size:
        np.testing.assert_array_equal(res[pid], np.asarray(engine.predict(own)))

    # remote rows == a local simulation of the peer's engine consuming its
    # requests in the requester-major order the answerer uses (worker 0's
    # ids first, then worker 1's)
    peer = 1 - pid
    sim = build_engine(peer)
    sim_out = {}
    for req in (0, 1):
        ids = traces[req][global2host[traces[req]] == peer]
        if ids.size:
            rows = np.asarray(sim.predict(ids))
            if req == pid:
                sim_out = dict(zip(ids.tolist(), rows))
    want = host2ids[peer]
    got = np.asarray(res[peer])
    for i, nid in enumerate(want):
        np.testing.assert_array_equal(got[i], sim_out[int(nid)])

    print(f"worker {pid} OK", flush=True)


def main() -> None:
    pid = int(sys.argv[1])
    port = sys.argv[2]
    if len(sys.argv) > 3 and sys.argv[3] in ("train", "train_topo"):
        train_main(pid, port, topo=sys.argv[3] == "train_topo")
        return
    if len(sys.argv) > 3 and sys.argv[3] == "serve":
        serve_main(pid, port)
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "")

    import jax

    # jax may have been imported before the env var was set (it is read at
    # import); the config update is authoritative (same as tests/conftest.py)
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2, jax.device_count()

    import numpy as np
    from jax.sharding import Mesh

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from quiver_tpu.comm import TpuComm

    R, D = 8, 4
    # host h's local block: row r = [1000*h + r, ...] so provenance is checkable
    local_table = (
        np.arange(R, dtype=np.float32)[:, None] + 1000.0 * pid + np.zeros((R, D), np.float32)
    )

    mesh = Mesh(np.array(jax.devices()), ("host",))
    comm = TpuComm(rank=pid, world_size=2, mesh=mesh)
    comm.static_budget = 4
    comm.register_local_table(pid, local_table)  # own block ONLY

    # host 0 asks host 1 for its local rows [1, 3]; host 1 asks host 0 for [2, 5, 7]
    if pid == 0:
        host2ids = [np.array([], np.int64), np.array([1, 3], np.int64)]
    else:
        host2ids = [np.array([2, 5, 7], np.int64), np.array([], np.int64)]

    res = comm.exchange(host2ids)

    peer = 1 - pid
    got = np.asarray(res[peer])
    want_rows = host2ids[peer]
    expect = want_rows[:, None] + 1000.0 * peer + np.zeros((want_rows.size, D), np.float32)
    np.testing.assert_allclose(got, expect)
    assert res[pid] is None  # no self-request was made

    # a second exchange reuses the same program/budget (steady-state path)
    res2 = comm.exchange(host2ids)
    np.testing.assert_allclose(np.asarray(res2[peer]), expect)

    # --- full DistFeature stack across the two processes: each host holds
    # ONLY its own rows; lookups use GLOBAL ids and the remote rows arrive
    # through the collective exchange (reference train_quiver_multi_node.py
    # needed a live cluster for this; here it is hermetic)
    from quiver_tpu import DistFeature, Feature, PartitionInfo

    n_global = 2 * R
    global2host = (np.arange(n_global) // R).astype(np.int32)  # host h owns [h*R,(h+1)*R)
    owned_global = np.arange(pid * R, (pid + 1) * R, dtype=np.int64)

    feat = Feature(rank=0, device_list=[0], device_cache_size=R * D * 4)
    feat.from_cpu_tensor(local_table)
    feat.set_local_order(owned_global)

    info = PartitionInfo(device=0, host=pid, hosts=2, global2host=global2host)
    dist = DistFeature(feat, info, comm)
    # every host requests the same mix of local + remote global ids
    want = np.array([1, R + 2, 3, 2 * R - 1], np.int64)
    got = np.asarray(dist[want])
    expect_rows = (want % R)[:, None] + 1000.0 * (want // R)[:, None] + np.zeros(
        (want.size, D), np.float32
    )
    np.testing.assert_allclose(got, expect_rows)

    print(f"worker {pid} OK", flush=True)


if __name__ == "__main__":
    main()
