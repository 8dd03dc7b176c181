"""One deterministic sharded-train case, shared verbatim by the
single-controller test and the 2-process `jax.distributed` worker
(VERDICT r4 item 6): both build the IDENTICAL (dp=1, ici=2) step — same
graph, params, keys, mesh shape — so the loss must agree to float
tolerance; only the process layout differs. Closest reference analog:
tests/python/cuda/test_comm.py:281-358 (needed a live cluster)."""

import numpy as np

CASE_SEEDS = np.arange(8, dtype=np.int32)
CASE_SIZES = (4, 4)


def build_case():
    import jax
    import jax.numpy as jnp
    import optax

    from __graft_entry__ import _community_graph
    from quiver_tpu import CSRTopo
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import (
        make_mesh,
        make_sharded_topo_train_step,
        make_sharded_train_step,
    )
    from quiver_tpu.parallel.collectives import pad_to_multiple
    from quiver_tpu.parallel.topology import build_topology_shards
    from quiver_tpu.pyg.sage_sampler import sample_dense_pure

    edge_index, feat, labels, n = _community_graph()
    topo = CSRTopo(edge_index=edge_index)
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-2)
    ds0 = sample_dense_pure(
        jnp.asarray(topo.indptr.astype(np.int32)),
        jnp.asarray(topo.indices.astype(np.int32)),
        jax.random.key(0), jnp.asarray(CASE_SEEDS), CASE_SIZES,
    )
    x0 = jnp.zeros((ds0.n_id.shape[0], feat.shape[1]), jnp.float32)
    params = model.init(jax.random.key(1), x0, ds0.adjs)
    # row-sharded topology blocks for the 2-shard (ici=2) mesh; both runners
    # place the window/indices blocks striped over ici
    win_b, idx_b, row_start = build_topology_shards(
        topo.indptr.astype(np.int32), topo.indices.astype(np.int32), 2
    )
    return {
        "indptr": topo.indptr.astype(np.int32),
        "indices": topo.indices.astype(np.int32),
        "stopo_np": (win_b, idx_b, np.asarray(row_start)),
        # the exact padding shard_feature_rows applies on an ici=2 mesh
        "feat_padded": np.asarray(pad_to_multiple(feat, 2)),
        "labels": labels,
        "params_np": jax.tree_util.tree_map(np.asarray, params),
        "opt_np": jax.tree_util.tree_map(np.asarray, tx.init(params)),
        "make_mesh": lambda: make_mesh(2),
        "make_step": lambda mesh: make_sharded_train_step(
            mesh, model, tx, sizes=CASE_SIZES, pipeline="dedup"
        ),
        "make_step_topo": lambda mesh: make_sharded_topo_train_step(
            mesh, model, tx, sizes=CASE_SIZES, pipeline="dedup"
        ),
    }
