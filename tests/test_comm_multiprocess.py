"""Hermetic 2-process multi-host exchange (VERDICT r1 item 6).

Spawns two real OS processes that bootstrap `jax.distributed` over a local
coordinator and run TpuComm.exchange with per-process table shards — the
execution mode a real multi-host TPU pod uses, which the single-controller
tests cannot cover. No process ever holds the global feature table.
"""

import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(mode=None):
    port = _free_port()
    env = dict(os.environ)
    # each worker must boot its own jax: drop the parent suite's virtual
    # 8-device CPU forcing and let the worker set platform itself
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_NUM_CPU_DEVICES", "1")
    argv_tail = [mode] if mode else []
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(pid), str(port), *argv_tail],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("distributed workers timed out:\n" + "\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"worker {pid} OK" in out, out
    return outs


pytestmark = pytest.mark.multiprocess  # 2-OS-process tests (see pytest.ini)


def test_two_process_exchange():
    _run_workers()


def test_two_process_serve_exchange_bit_parity():
    """`TpuComm.exchange_serve` across two REAL processes: each holds only
    its seed-ownership shard (community-closed topology + owned feature
    rows) and answers routed sub-batches through its local pipelined
    `ServeEngine`; every remote logits row must bit-match a local
    simulation of the peer's engine. The multi-process leg of the
    distributed serving tentpole (single-controller coverage lives in
    tests/test_serve_dist.py)."""
    _run_workers(mode="serve")


def test_two_process_sharded_train_step_matches_single_controller():
    """One `make_sharded_train_step` step on a PROCESS-SPANNING (dp=1,
    ici=2) mesh (two OS processes, one device each, jax.distributed) must
    produce the same loss as the identical step on a single-controller
    2-device mesh — same case, params, and keys (tests/sharded_train_case
    is the single source of both)."""
    from sharded_train_case import CASE_SEEDS, build_case

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    case = build_case()
    mesh = case["make_mesh"]()  # first 2 of the suite's virtual devices
    step = case["make_step"](mesh)

    def put(x, spec=P()):
        return jax.device_put(jax.numpy.asarray(x), NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map(put, case["params_np"])
    opt_state = jax.tree_util.tree_map(put, case["opt_np"])
    _, _, loss = step(
        params, opt_state, jax.random.key(2),
        put(case["indptr"]), put(case["indices"]),
        put(case["feat_padded"], P(("ici",), None)),
        put(case["labels"]), put(CASE_SEEDS, P("dp")),
    )
    expect = float(loss)
    assert np.isfinite(expect)

    outs = _run_workers(mode="train")
    for pid, out in enumerate(outs):
        line = [l for l in out.splitlines() if l.startswith(f"worker {pid} loss")]
        assert line, out
        got = float(line[0].split()[-1])
        assert abs(got - expect) < 1e-5, (got, expect, out)


def test_two_process_topo_train_step_matches_single_controller():
    """`make_sharded_topo_train_step` end to end across two OS processes:
    each process holds ONLY its own block of the row-sharded CSR, and one
    step must produce the same loss as the identical single-controller
    run."""
    from sharded_train_case import CASE_SEEDS, build_case

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quiver_tpu.parallel import ShardedTopology

    case = build_case()
    mesh = case["make_mesh"]()
    step = case["make_step_topo"](mesh)

    def put(x, spec=P()):
        return jax.device_put(jax.numpy.asarray(x), NamedSharding(mesh, spec))

    win_b, idx_b, row_start = case["stopo_np"]
    stopo = ShardedTopology(
        windows=put(win_b, P(("ici",), None, None)),
        indices=put(idx_b, P(("ici",), None)),
        row_start=put(row_start),
    )
    params = jax.tree_util.tree_map(put, case["params_np"])
    opt_state = jax.tree_util.tree_map(put, case["opt_np"])
    _, _, loss = step(
        params, opt_state, jax.random.key(2), stopo,
        put(case["feat_padded"], P(("ici",), None)),
        put(case["labels"]), put(CASE_SEEDS, P("dp")),
    )
    expect = float(loss)
    assert np.isfinite(expect)

    outs = _run_workers(mode="train_topo")
    for pid, out in enumerate(outs):
        line = [l for l in out.splitlines() if l.startswith(f"worker {pid} loss")]
        assert line, out
        got = float(line[0].split()[-1])
        assert abs(got - expect) < 1e-5, (got, expect, out)
