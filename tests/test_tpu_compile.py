"""Ask the TPU's own compiler, with no TPU attached.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). Four programs of the
main path, at the shapes `chip_smoke.py` runs, are lowered for a described
``v5e:2x2`` and must be accepted and fit one chip's 16 GB:

  (a) fused train step      (sample -> gather -> fwd/bwd -> adam, one program)
  (b) dedup train step      (the same over the capped dedup sampler)
  (c) one sealed serve bucket from `inference.make_serve_step`, seed buffer
      donated as `BucketPrograms` donates it
  (d) `make_sharded_topo_train_step` on the four described devices, at the
      products shape (the shape PR 28 measured on four chips)
  (e) the two programs of `Feature.lookup_padded`, at the shapes of the
      benchmark's train cells: the row gather and nothing beside it
  (f) `make_sharded_topo_train_step` at the shard sizes of the benchmark's
      four-chip cell (half of ogbn-papers100M: 7.1 GB of
      feature rows and 0.86 GB of graph a chip): eleven seconds, where
      one-element gathers from a 1-D edge array of that size took minutes
  (g) the jitted optax step over `GraphSAGE` at the shapes of the benchmark's
      igb cell (explicit ``cols``): the first layer's k-fold gather is in no
      buffer, and the program's temporaries stay under 2 GiB
  (h) `GraphSageSampler.sample_dense`'s one program (`_sample_program`,
      `jit_sample_dense_program`) at the shapes of the benchmark's two
      one-chip train cells: the graph arrays are parameters, no constant of
      their size is in the program, temporaries stay under 0.5 GiB
  (i) the jitted optax step over `GAT` (4 heads x 128, 4 output heads) at the
      shapes of the benchmark's attention cell: no ``[W_dst, k, H, D]`` array
      in any buffer, temporaries under 4.5 GiB, five matrix products and no
      more (scores, softmax and the weighted sum are not products), and every
      pattern of the cell's two by-shape metrics meets an operation

A compile that passes is not a chip run. To stay inside the suite's time
limit the tests compile (b) at batch 64 and (c) at bucket 8 (the graph and
feature tables, which decide what fits, keep their full size);
``python tests/test_tpu_compile.py`` compiles all four at full size and
prints what the compiler reports.
"""

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chip_smoke import PRODUCTS, SIZES, make_model, make_train_step, ring_sampler
from quiver_tpu.feature import _padded_gather, _padded_gather_ordered
from quiver_tpu.inference import make_serve_step
from quiver_tpu.ops.sample import LANE, far_width, pad_widths, tiled_sample_layer
from quiver_tpu.parallel import make_sharded_topo_train_step, make_sharded_train_step
from quiver_tpu.parallel.topology import ShardedTopology
from quiver_tpu.pyg.sage_sampler import sample_dense_fused, sample_dense_pure

HBM_BYTES = 16e9                      # one v5e chip
N = PRODUCTS["nodes"]
TILE_ROWS = 2_833_089                 # [M, 128] tile table of the seed-0 graph
DEDUP_CAPS = (16384, 151552, 600064)  # at batch 1024, margin 1.2
SERVE_BUCKET = 64
# per-shard rows of the four-way edge-balanced split, with slack
SHARD_ROWS = 700_000


def _struct(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    # a program compiled for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next run would warn
    # and compile again): cache off around these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _graph_structs():
    return _sds((N, 2), jnp.int32), _sds((TILE_ROWS, LANE), jnp.int32)


def _train_step(model, tx, dedup_caps):
    """The smoke's train step as ONE program: `sample_dense_fused` (or
    `sample_dense_pure` with caps) over the tile layout -> gather ->
    `chip_smoke.make_train_step`."""
    train_step = make_train_step(model, tx)

    def step(params, opt_state, bd, tiles, table, labels, key, seeds):
        key, sub = jax.random.split(key)

        def hop(cur, cur_valid, k, hkey):
            return tiled_sample_layer(bd, tiles, cur, cur_valid, k, hkey)

        if dedup_caps is None:
            ds = sample_dense_fused(None, None, sub, seeds, SIZES, sample_fn=hop)
        else:
            ds = sample_dense_pure(None, None, sub, seeds, SIZES, dedup_caps,
                                   sample_fn=hop)
        x = jnp.take(table, jnp.clip(ds.n_id, 0, table.shape[0] - 1), axis=0)
        return train_step(params, opt_state, key, x, ds.adjs, jnp.take(labels, seeds))

    return step


def _model_structs(model, tx):
    """(params, opt_state, key) shapes, traced from a one-seed sample."""
    ds = ring_sampler(dedup=False).sample_dense(np.arange(1))
    x = jnp.zeros((ds.n_id.shape[0], PRODUCTS["dim"]), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(lambda k: model.init(k, x, ds.adjs), key)
    return params, jax.eval_shape(tx.init, params), key


def _fits(compiled, what):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, f"{what}: {need / 1e9:.2f} GB on one chip"
    return {"arguments_gb": round(m.argument_size_in_bytes / 1e9, 2),
            "temporaries_gb": round(m.temp_size_in_bytes / 1e9, 2)}


def compile_train_step(v5e, dedup_caps, batch):
    model, tx = make_model(PRODUCTS["classes"]), optax.adam(3e-3)
    params, opt_state, key = _model_structs(model, tx)
    bd, tiles = _graph_structs()
    args = (params, opt_state, bd, tiles, _sds((N, PRODUCTS["dim"]), jnp.float32),
            _sds((N,), jnp.int32), key, _sds((batch,), jnp.int32))
    one_chip = SingleDeviceSharding(v5e.devices[0])
    compiled = jax.jit(_train_step(model, tx, dedup_caps)).lower(
        *_struct(args, one_chip)).compile()
    return _fits(compiled, f"train step caps={dedup_caps} batch={batch}")


def compile_serve_bucket(v5e, bucket=SERVE_BUCKET):
    model, tx = make_model(PRODUCTS["classes"]), optax.adam(3e-3)
    params, _, key = _model_structs(model, tx)
    # a small real sampler supplies the serve step; the shapes it is lowered
    # at are the products ones
    serve_step, _, id_dtype = make_serve_step(model, ring_sampler())
    one_chip = SingleDeviceSharding(v5e.devices[0])
    args = _struct(
        (params, key, _sds((), jnp.uint32), _sds((bucket,), id_dtype),
         _sds((N, PRODUCTS["dim"]), jnp.float32)), one_chip)
    # (params, key0, call, seeds, ...): the seed buffer is argument 3
    compiled = jax.jit(serve_step, donate_argnums=(3,)).lower(
        *args, None, _struct(_graph_structs(), one_chip)).compile()
    return _fits(compiled, f"serve bucket {bucket}"), compiled.as_text()


def compile_sharded_topo_step(v5e, n_devices=4, batch=PRODUCTS["batch"]):
    """n_devices=1 is the one-device twin `chip_smoke.py --chips 4` compares
    the sharded steps against (script only)."""
    from quiver_tpu.parallel.topology import _padded

    model, tx = make_model(PRODUCTS["classes"], dropout=0.0), optax.adam(3e-3)
    params, opt_state, key = _model_structs(model, tx)
    mesh = Mesh(np.array(v5e.devices[:n_devices]).reshape(1, n_devices), ("dp", "ici"))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("ici", None))
    # blocks of whole (8, 128) tiles, as `shard_topology_rows` cuts them
    shard_rows = SHARD_ROWS if n_devices == 4 else N
    stopo = ShardedTopology(
        windows=jax.ShapeDtypeStruct(
            (n_devices, _padded(shard_rows, 8 * LANE), 2), jnp.int32,
            sharding=NamedSharding(mesh, P("ici", None, None))),
        indices=jax.ShapeDtypeStruct(
            (n_devices, _padded(PRODUCTS["edges"] // n_devices, 8 * LANE)), jnp.int32,
            sharding=rows),
        row_start=jax.ShapeDtypeStruct((n_devices + 1,), jnp.int32, sharding=rep),
    )
    n_pad = -(-N // n_devices) * n_devices
    step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused")
    compiled = step.lower(
        *_struct((params, opt_state, key), rep), stopo,
        jax.ShapeDtypeStruct((n_pad, PRODUCTS["dim"]), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((N,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((batch,), jnp.int32,
                             sharding=NamedSharding(mesh, P("dp"))),
    ).compile()
    if n_devices > 1:
        assert "all-reduce" in compiled.as_text(), (
            "the sharded step compiled without a collective")
    return _fits(compiled, f"sharded-topology step on {n_devices} device(s)")


# papers100M-sage.train-sharded4 (qbench/configs/papers100M-sage.json): nodes,
# edges, lanes, classes; the per-shard block sizes `shard_topology_rows` gives every seed
PAPERS = dict(nodes=55_529_978, edges=807_842_936, dim=128, classes=172,
              shard_rows=14_155_776, shard_edges=209_715_200)


def compile_flat_sharded_topo_step_at_papers_size(v5e, batch=1024):
    """What the four-chip cell runs, for four described chips: the blocks
    `shard_topology_rows` places for that graph, handed over as shapes."""
    from quiver_tpu.models import GraphSAGE

    model = GraphSAGE(hidden_dim=256, out_dim=PAPERS["classes"], num_layers=3, dropout=0.0)
    tx = optax.adam(1e-3)
    ds = ring_sampler(dedup=False).sample_dense(np.arange(1))
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((ds.n_id.shape[0], PAPERS["dim"])), ds.adjs), key)
    mesh = Mesh(np.array(v5e.devices[:4]).reshape(1, 4), ("dp", "ici"))
    rep = NamedSharding(mesh, P())
    blocks = NamedSharding(mesh, P("ici", None))
    stopo = ShardedTopology(
        windows=jax.ShapeDtypeStruct((4, PAPERS["shard_rows"], 2), jnp.int32,
                                     sharding=NamedSharding(mesh, P("ici", None, None))),
        indices=jax.ShapeDtypeStruct((4, PAPERS["shard_edges"]), jnp.int32, sharding=blocks),
        row_start=jax.ShapeDtypeStruct((5,), jnp.int32, sharding=rep))
    step = make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused")
    compiled = step.lower(
        *_struct((params, jax.eval_shape(tx.init, params), key), rep), stopo,
        jax.ShapeDtypeStruct((-(-PAPERS["nodes"] // 4) * 4, PAPERS["dim"]), jnp.float32,
                             sharding=blocks),
        jax.ShapeDtypeStruct((PAPERS["nodes"],), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=NamedSharding(mesh, P("dp"))),
    ).compile()
    text = compiled.as_text()
    assert "HloModule jit_sharded_topo_train_step" in text
    assert " all-reduce(" in text, "the sharded step compiled without a collective"
    # the edge block is read as 128-lane rows where it lies: no copy of it
    lane_rows = f"s32[{PAPERS['shard_edges'] // LANE},{LANE}]"
    assert re.search(rf"= {re.escape(lane_rows)}\S* bitcast\(", text), "no bitcast to lane rows"
    assert not re.search(rf"= {re.escape(lane_rows)}\S* copy\(", text)
    # nor of either block where the program drops its shard axis of length 1
    # (blocks are padded to whole (8, 128) tiles for this: `_flat_plan`)
    assert not re.search(r" reduce\(%param", text[text.index("ENTRY"):])
    # the window block is what was placed: the program builds nothing of a
    # shard's row count (PR 33: the parent stacked it from the local indptr,
    # a slice, two pads and an add of [R_max] a step); the compiler moves the
    # block to its faster memory space by an asynchronous copy, that is all
    assert _results_of_size(text, PAPERS["shard_rows"]) <= {
        "parameter", "bitcast", "copy-start", "copy-done"}
    assert re.search(rf"= s32\[1,{PAPERS['shard_rows']},2\]\S* parameter\(", text)
    return (_fits(compiled, "flat sharded-topology step at the papers100M cell's size"),
            _entry_operations(compiled))


def _results_of_size(text, size):
    """``{opcode}`` of every instruction of an optimized HLO text, fused
    bodies included, whose result (a tuple's elements too) has a dimension
    of ``size``."""
    has_size = re.compile(rf"\[(?:\d+,)*{size}(?:,\d+)*\]")
    lines = re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", text, re.M)
    return {opcode for result, opcode in lines if has_size.search(result)}


def _computations(text):
    """``{name: [instruction lines]}`` of an optimized HLO text."""
    out, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if head:
            body = out.setdefault(head.group(1), [])
        elif body is not None and " = " in line:
            body.append(line.strip())
    return out


def _tile_fetch_branches(text, width, k):
    """The `conditional` of `ops.sample._tiled_resolve` at a hop of ``width``
    seeds and fan-out ``k``: how many ``[width, 128]`` row gathers each of
    its two branches holds, (one-fetch branch, k-fetch branch)."""
    comps = _computations(text)
    (cond,) = [line for body in comps.values() for line in body
               if re.match(rf"(?:ROOT )?%\S+ = \(?s32\[{width},{k}\]\S* conditional\(", line)]
    k_fetch, one_fetch = re.search(r"branch_computations=\{%([\w.-]+), %([\w.-]+)\}", cond).groups()
    rows = re.compile(rf"(?:ROOT )?%\S+ = s32\[{width},{LANE}\]\S* fusion\(")
    return tuple(sum(bool(rows.match(line)) for line in comps[name]) for name in (one_fetch, k_fetch))


def _entry_operations(compiled):
    """The entry computation's instructions as a device trace names them:
    one line each, operands with their shapes."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    options.print_metadata = options.print_backend_config = False
    (module,) = compiled.runtime_executable().hlo_modules()
    text = module.to_string(options)
    return [line.strip().removeprefix("ROOT ")
            for line in text[text.index("ENTRY"):].splitlines() if " = " in line]


def compile_sharded_feature_step(v5e, n_devices, batch=PRODUCTS["batch"]):
    """`make_sharded_train_step` (graph replicated, feature rows striped) on a
    (dp=1, ici=n_devices) mesh of described devices. Script only: the suite
    keeps to four compiles, and this one takes two minutes (flat-CSR
    sampling: element gathers from a 123M-entry 1-D array)."""
    model, tx = make_model(PRODUCTS["classes"], dropout=0.0), optax.adam(3e-3)
    params, opt_state, key = _model_structs(model, tx)
    mesh = Mesh(np.array(v5e.devices[:n_devices]).reshape(1, n_devices), ("dp", "ici"))
    rep = NamedSharding(mesh, P())
    n_pad = -(-N // n_devices) * n_devices
    edges = PRODUCTS["edges"]
    step = make_sharded_train_step(mesh, model, tx, SIZES, pipeline="fused")
    compiled = step.lower(
        *_struct((params, opt_state, key, _sds((N + 1,), jnp.int32),
                  _sds((edges,), jnp.int32)), rep),
        jax.ShapeDtypeStruct((n_pad, PRODUCTS["dim"]), jnp.float32,
                             sharding=NamedSharding(mesh, P("ici", None))),
        jax.ShapeDtypeStruct((N,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((batch,), jnp.int32,
                             sharding=NamedSharding(mesh, P("dp"))),
    ).compile()
    return _fits(compiled, f"sharded feature step on {n_devices} device(s)")


# igb-small-sage.train-dedup (qbench/configs/igb-small-sage.json and the cell's
# caps): batch, fan-out innermost last, row lanes, hidden, classes
IGB = dict(batch=10240, fanout=(10, 15), dim=1024, hidden=128, classes=19)
IGB_CAPS = (73728, 417792)           # `calibrate_caps(margin=1.1)`, as the cell fixes them
IGB_CAPS_DEFAULT = (81920, 458752)   # the same probes at its default margin 1.2


def _igb_step_args(model, tx, caps):
    """(params, opt_state, key, x, adjs, labels) shapes of a step over
    explicit-``cols`` hops at the igb cells' widths."""
    from quiver_tpu.pyg.sage_sampler import DenseAdj

    widths = (IGB["batch"],) + tuple(caps)
    scalar = _sds((), jnp.int32)
    # outermost hop first: hop i aggregates widths[i + 1] source rows into widths[i]
    adjs = tuple(
        DenseAdj(cols=_sds((widths[i], k), jnp.int32), mask=_sds((widths[i], k), jnp.bool_),
                 n_src=scalar, n_dst=scalar)
        for i, k in reversed(list(enumerate(IGB["fanout"]))))
    x = _sds((widths[-1], IGB["dim"]), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(model.init, key, x, adjs)
    return (params, jax.eval_shape(tx.init, params), key, x, adjs,
            _sds((IGB["batch"],), jnp.int32))


def compile_igb_model_step(v5e, caps):
    """The step of `examples/reddit_sage.py` over explicit-``cols`` hops at the
    igb cell's widths: (entry operations as `(shape, opcode)`, temporaries)."""
    from quiver_tpu.models import GraphSAGE

    model = GraphSAGE(hidden_dim=IGB["hidden"], out_dim=IGB["classes"],
                      num_layers=len(IGB["fanout"]), dropout=0.0)
    tx = optax.adam(0.01)
    args = _igb_step_args(model, tx, caps)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    compiled = make_train_step(model, tx).lower(*_struct(args, one_chip)).compile()
    _fits(compiled, f"igb model step at caps {caps}")
    text = compiled.as_text()
    ops = re.findall(r"^\s+(?:ROOT )?%\S+ = (\S+?)\{\S* ([\w-]+)\((?:.*custom_call_target=\"(\w+)\")?",
                     text[text.index("ENTRY"):], re.M)
    # a Pallas kernel is a custom-call to "tpu_custom_call": name it so
    ops = [(shape, target or op) for shape, op, target in ops]
    return ops, compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("caps,temp_gib", [(IGB_CAPS, 2.0), (IGB_CAPS_DEFAULT, 2.25)],
                         ids=["margin1.1", "margin1.2"])
def test_igb_model_step_holds_no_k_fold_gather_on_v5e(v5e, caps, temp_gib):
    """ROADMAP S2 / R-M6: the first layer's `[W_dst * k, D]` gather (4.5 GB at
    the cell's caps) is in no buffer of the step, in either layout; the rows
    go through `gather_masked_sum`'s kernel; the program loads at
    `calibrate_caps`' default margin too."""
    ops, temp_bytes = compile_igb_model_step(v5e, caps)
    w_dst, k = caps[0], IGB["fanout"][-1]
    shapes = {shape for shape, _ in ops}
    assert f"f32[{w_dst * k},{IGB['dim']}]" not in shapes, shapes
    assert f"f32[{w_dst},{k},{IGB['dim']}]" not in shapes
    assert f"f32[{k},{w_dst},{IGB['dim']}]" not in shapes
    # one kernel call, for the 4 KB rows of layer 1; layer 2's 512 B rows stay XLA's
    assert [shape for shape, op in ops if op == "tpu_custom_call"] == [
        f"f32[{w_dst},1,{IGB['dim']}]"], ops
    # the relaid x (1.6 GiB at the cell's caps) and the kernel's output, no more:
    # 1.88 and 2.06 GiB, where the `take -> sum` form needed 8.72 GiB in one block
    assert temp_bytes < temp_gib * 2**30, temp_bytes / 2**30


def compile_igb_gat_step(v5e, caps=IGB_CAPS):
    """The benchmark's attention step (`qbench.kinds.train.make_train_step` over
    `GAT` as `qbench/configs/igb-small-gat.json` states it) over explicit-``cols``
    hops at the cell's widths: (compiled, the entry's operations as a device
    trace names them; a loop is one of them, its body is not listed)."""
    from qbench.kinds import train
    from quiver_tpu.models import GAT

    model = GAT(hidden_dim=IGB["hidden"], out_dim=IGB["classes"], heads=4, out_heads=4,
                num_layers=len(IGB["fanout"]), dropout=0.0, activation=jax.nn.relu)
    tx = optax.adam(0.01)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    compiled = train.make_train_step(model, tx, None).lower(
        *_struct(_igb_step_args(model, tx, caps), one_chip)).compile()
    return compiled, _entry_operations(compiled)


def test_igb_gat_step_fits_a_v5e_and_its_metrics_patterns_meet_operations(v5e):
    """The attention cell's step: it fits beside the cell's 6.8 GB resident,
    lays no k-fold rows out, multiplies matrices in the projections alone, and
    every pattern of `gat_project_ms.train` and `gat_edge_ms.train` names at
    least one operation of the optimized program (a later restructuring fails
    here instead of emptying a metric)."""
    import json

    compiled, operations = compile_igb_gat_step(v5e)
    _fits(compiled, "igb GAT step")
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"igb GAT step: temporaries {temp / 2**30:.2f} GiB")
    assert temp < 4.5 * 2**30 and temp + 6.8e9 < 0.95 * 16.9e9, temp / 2**30
    text = compiled.as_text()
    w_dst, k = IGB_CAPS[0], IGB["fanout"][-1]
    for shape in (f"[{w_dst},{k},4,128]", f"[{k},{w_dst},4,128]", f"[{w_dst * k},512]",
                  f"[{k},{w_dst},512]", f"[{w_dst},{k},512]", f"[{w_dst * k},4,128]"):
        assert f"f32{shape}" not in text, shape
    # projection forward x2, weight gradient x2, layer 2's input gradient: no other product
    assert len(re.findall(r" convolution\(", text)) == 5 and " dot(" not in text
    matched = {}
    for metric in ("gat_project_ms.train", "gat_edge_ms.train"):
        with open(os.path.join(REPO, "qbench", "metrics", f"{metric}.json")) as f:
            params = json.load(f)["params"]
        assert params["line"] == "ops" and not params.get("exclude")
        for pattern in params["include"]:
            assert any(re.search(pattern, op) for op in operations), (metric, pattern)
        matched[metric] = [op for op in operations
                           if any(re.search(p, op) for p in params["include"])]
    # layer 1's forward product and its weight gradient; its two loops, forward and backward
    assert len(matched["gat_project_ms.train"]) == 2, matched["gat_project_ms.train"]
    assert sum(" while(" in op for op in matched["gat_edge_ms.train"]) == 2


def test_fused_train_step_compiles_for_v5e(v5e):
    compile_train_step(v5e, None, PRODUCTS["batch"])


def test_dedup_train_step_compiles_for_v5e(v5e):
    # batch 64 with the caps scaled to match: the batch-1024 compile alone
    # takes two minutes (run this file as a script for it)
    compile_train_step(v5e, (1024, 10240, 40960), 64)


def test_serve_bucket_compiles_for_v5e(v5e):
    # hops of 8, 128 and 1408 seeds keep the tile layout's k-fetch (as every
    # hop of the benchmark's serve cell does, 704 seeds at the widest): no
    # branch in the program, which is the parent's text for text
    assert " conditional(" not in compile_serve_bucket(v5e, 8)[1]


def test_sharded_topo_step_compiles_for_four_v5e_at_products_size(v5e):
    compile_sharded_topo_step(v5e)


def test_flat_sharded_topo_step_compiles_for_four_v5e_at_papers_size(v5e):
    fit, operations = compile_flat_sharded_topo_step_at_papers_size(v5e)
    assert 7.5 < fit["arguments_gb"] < 9.0  # ~48% of a chip, as the cell states
    # the benchmark's per-layer patterns for this one-program step, against
    # the program: which operations each metric of the cell would add up
    import json

    def matched(metric):
        path = os.path.join(REPO, "qbench", "metrics", f"{metric}.json")
        with open(path) as f:
            params = json.load(f)["params"]
        return [op for op in operations
                if any(re.search(p, op) for p in params["include"])
                and not any(re.search(p, op) for p in params.get("exclude", ()))]

    assert len(matched("collective_ms.train")) == 5  # 3 neighbour/mask pairs, 2 of rows
    gathers = matched("shard_gather_ms.train")
    # seeds, hop 1, hop 2 and the leaves: a row gather and an owner-mask select each
    assert len(gathers) == 8 and all(re.match(r"%\S+ = f32\[\d+,128\]", op) for op in gathers)
    sampling = matched("shard_sample_ms.train")
    lane_rows = [op for op in sampling
                 if re.search(r"= s32\[\d+,128\]\S* fusion\(s32\[\d+,128\]", op)]
    assert len(lane_rows) == sum(SIZES)  # one lane-row gather a drawn position
    assert not any(re.match(r"%\S+ = \(?(f32|bf16)\[", op) for op in sampling)
    assert not set(sampling) & set(gathers)


# the two one-chip train cells (qbench/configs/*.json, qbench/workloads/*.json;
# tile rows of the stand-in graph, the same for every seed: one degree multiset)
TRAIN_CELLS = {
    "products-sage.train-fused": dict(
        nodes=N, tile_rows=2_794_186, batch=1024, sizes=(15, 10, 5), dedup=False,
        caps=None),
    "igb-small-sage.train-dedup": dict(
        nodes=1_000_000, tile_rows=1_007_613, batch=IGB["batch"], sizes=IGB["fanout"],
        dedup=True, caps=IGB_CAPS),
}


def compile_sample_dense_program(v5e, nodes, tile_rows, batch, sizes, dedup, caps):
    """`sample_dense`'s program lowered for the described chip over a tiled
    graph of the cell's size handed over as shapes: (optimized HLO text,
    `memory_analysis`, seconds)."""
    from quiver_tpu import trace
    from quiver_tpu.pyg.sage_sampler import sample_dense_program

    one_chip = SingleDeviceSharding(v5e.devices[0])
    graph = (_sds((nodes, 2), jnp.int32), _sds((tile_rows, LANE), jnp.int32))
    key0 = jax.eval_shape(lambda: jax.random.key(0))
    t0 = time.time()
    compiled = sample_dense_program.lower(
        *_struct((key0, _sds((), jnp.uint32), _sds((batch,), jnp.int32), graph), one_chip),
        sizes=tuple(sizes), caps=caps, dedup=dedup, hop=("tiled", False, 512)).compile()
    seconds = time.time() - t0
    text = compiled.as_text()
    (name,) = trace.SAMPLE_PROGRAM_NAMES
    assert f"HloModule jit_{name}" in text
    _fits(compiled, f"{name} batch={batch} sizes={sizes} caps={caps}")
    return text, compiled.memory_analysis(), seconds


@pytest.mark.parametrize("cell", list(TRAIN_CELLS))
def test_sample_dense_program_compiles_for_v5e_at_the_train_cells_shapes(v5e, cell):
    shape = TRAIN_CELLS[cell]
    text, memory, seconds = compile_sample_dense_program(v5e, **shape)
    print(f"{cell}: jit_sample_dense_program compiled in {seconds:.1f}s, "
          f"temporaries {memory.temp_size_in_bytes / 2**30:.3f} GiB")
    # the graph is what the program is handed, never what it holds
    bd, tiles = f"s32[{shape['nodes']},2]", f"s32[{shape['tile_rows']},{LANE}]"
    entry = text[text.index("ENTRY"):]
    for array in (bd, tiles):
        assert re.search(rf"= {re.escape(array)}\S* parameter\(", entry), array
        assert not re.search(rf"= {re.escape(array)}\S* constant\(", text), array
    graph_bytes = 4 * (2 * shape["nodes"] + LANE * shape["tile_rows"])
    assert graph_bytes <= memory.argument_size_in_bytes < graph_bytes + 2**20
    assert memory.temp_size_in_bytes < 0.5 * 2**30, memory.temp_size_in_bytes / 2**30
    # the tile fetch (`ops.sample._tiled_resolve`, PR 35): at every hop of
    # 8,192 seeds and more, a branch with ONE first-row gather a seed (the
    # far seeds' list is an eighth as wide) beside the k-fetch's k
    widths = pad_widths(shape["batch"], shape["sizes"], shape["caps"])
    engaged = [(width, k) for width, k in zip(widths, shape["sizes"]) if far_width(width, k)]
    assert text.count(" conditional(") == len(engaged)  # the smaller hops hold no branch
    for width, k in engaged:
        assert _tile_fetch_branches(text, width, k) == (1, k), (width, k)


# papers100M-sage-tiered.train-hot6g (qbench/workloads): half of
# ogbn-papers100M on ONE chip, 6 GiB of hot rows, the flat graph as lane rows
TIERED = dict(caps=(12288, 94208, 499712), cold_cap=143360, hot_rows=6 * 2**30 // 512,
              edge_rows=-(-807_842_936 // LANE))


def compile_flat_dedup_sampler_at_papers_size(v5e, caps=TIERED["caps"], batch=1024):
    """`sample_dense`'s program over the FLAT layout at the tiered cell's
    size: (optimized HLO text, `memory_analysis`, seconds, the sample's
    shapes)."""
    from quiver_tpu.pyg.sage_sampler import sample_dense_program

    one_chip = SingleDeviceSharding(v5e.devices[0])
    graph = (_sds((PAPERS["nodes"], 2), jnp.int32), _sds((TIERED["edge_rows"], LANE), jnp.int32))
    key0 = jax.eval_shape(lambda: jax.random.key(0))
    args = _struct((key0, _sds((), jnp.uint32), _sds((batch,), jnp.int32), graph), one_chip)
    static = dict(sizes=SIZES, caps=tuple(caps), dedup=True, hop=("flat", False, 512))
    t0 = time.time()
    compiled = sample_dense_program.lower(*args, **static).compile()
    seconds = time.time() - t0
    _fits(compiled, f"flat sample_dense_program caps={caps}")
    ds = jax.eval_shape(lambda *a: sample_dense_program(*a, **static), *args)
    return compiled.as_text(), compiled.memory_analysis(), seconds, ds


def compile_tiered_train_step_at_papers_size(v5e, ds, batch=1024):
    """`make_tiered_train_step`'s program at the tiered cell's shapes, the
    hot table and the labels handed over as shapes."""
    from quiver_tpu import trace
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.pipeline import TieredBatch, make_tiered_train_step

    one_chip = SingleDeviceSharding(v5e.devices[0])
    model = GraphSAGE(hidden_dim=256, out_dim=PAPERS["classes"],
                      num_layers=len(SIZES), dropout=0.0)
    tx = optax.adam(1e-3)
    width = ds.n_id.shape[0]
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(
        lambda k, adjs: model.init(k, jnp.zeros((width, PAPERS["dim"]), jnp.float32), adjs),
        key, ds.adjs)
    batch_s = TieredBatch(ds=ds._replace(batch_size=None), mapped=_sds((width,), jnp.int32),
                          cold_rows=_sds((TIERED["cold_cap"], PAPERS["dim"]), jnp.float32),
                          cold_pos=None, seeds=_sds((batch,), jnp.int32))
    step = make_tiered_train_step(model, tx, np.zeros(1, np.int32),
                                  np.zeros((1, PAPERS["dim"]), np.float32)).program
    args = (params, jax.eval_shape(tx.init, params), key,
            _sds((TIERED["hot_rows"], PAPERS["dim"]), jnp.float32),
            _sds((PAPERS["nodes"],), jnp.int32), batch_s)
    t0 = time.time()
    compiled = step.lower(*_struct(args, one_chip)).compile()
    seconds = time.time() - t0
    text = compiled.as_text()
    assert f"HloModule jit_{trace.TIERED_PROGRAM_NAMES[0]}" in text
    return text, _fits(compiled, "tiered_train_step"), seconds, _entry_operations(compiled)


def test_tiered_step_and_flat_sampler_compile_for_v5e_at_papers_size(v5e):
    """The two programs a step of papers100M-sage-tiered.train-hot6g launches:
    the three-hop dedup sampler over the flat graph seen as 128-lane rows
    (no one-element gather from the 8e8-entry edge array) and the tiered
    step (hot gather, merge of the cold block, model, Adam)."""
    text, memory, seconds, ds = compile_flat_dedup_sampler_at_papers_size(v5e)
    print(f"flat jit_sample_dense_program at papers size compiled in {seconds:.1f}s, "
          f"temporaries {memory.temp_size_in_bytes / 2**30:.3f} GiB")
    rows = f"s32[{TIERED['edge_rows']},{LANE}]"
    entry = text[text.index("ENTRY"):]
    assert re.search(rf"= {re.escape(rows)}\S* parameter\(", entry)
    assert " conditional(" not in text  # `flat_resolve` fetches every position: no branch
    # every fetch from the edges is a row gather: [W, 128] out of [R, 128]
    fetches = re.findall(rf"\(param_\S+: {re.escape(rows)}, param_\S+: s32\[(\d+)\]\) -> (\S+) ", text)
    assert len(fetches) == sum(SIZES), fetches  # one row gather a drawn position
    assert all(out == f"s32[{width},{LANE}]" for width, out in fetches), fetches
    # the (first edge, degree) table is the placed argument and nothing of
    # the node count's size is built in the launch (PR 33: the parent's
    # in-program stack was four such operations and 1.04 GiB of temporaries)
    assert re.search(rf"= s32\[{PAPERS['nodes']},2\]\S* parameter\(", entry)
    assert _results_of_size(text, PAPERS["nodes"]) == {"parameter"}
    assert not _results_of_size(text, PAPERS["nodes"] + 1)
    assert memory.temp_size_in_bytes < 0.1 * 2**30

    text, fit, seconds, operations = compile_tiered_train_step_at_papers_size(v5e, ds)
    print(f"jit_tiered_train_step compiled in {seconds:.1f}s: {fit}")
    assert 6.4 < fit["arguments_gb"] < 7.2  # the hot table, the labels, one batch
    import json

    with open(os.path.join(REPO, "qbench", "metrics", "cold_merge_ms.train.json")) as f:
        params = json.load(f)["params"]
    merge = [op for op in operations if any(re.search(p, op) for p in params["include"])]
    print("\n".join(merge))
    assert merge, "cold_merge_ms.train's patterns match no operation of the tiered step"
    # the gather out of the cold block and the select over the two gathers
    assert len(merge) == 2 and all(
        re.match(rf"%\S+ = f32\[{ds.n_id.shape[0]},{PAPERS['dim']}\]", op) for op in merge)
    assert f"f32[{TIERED['cold_cap']},{PAPERS['dim']}]" in merge[0] and "pred[" in merge[1]
    hot = f"f32[{TIERED['hot_rows']},{PAPERS['dim']}]"
    assert not any(hot in op for op in merge)  # the hot gather is the gather's, not the merge's


def compile_feature_gather(v5e, program, rows, dim, positions):
    """The entry computation of a `lookup_padded` program as
    ``[(shape, opcode)]``."""
    args = [_sds((rows, dim), jnp.float32), _sds((positions,), jnp.int32)]
    if program is _padded_gather_ordered:
        args.insert(1, _sds((rows,), jnp.int32))  # `order`
    one_chip = SingleDeviceSharding(v5e.devices[0])
    text = program.lower(*_struct(args, one_chip)).compile().as_text()
    return re.findall(r"^\s+(?:ROOT )?%\S+ = (\S+?)\{\S* ([\w-]+)\(",
                      text[text.index("ENTRY"):], re.M)


@pytest.mark.parametrize("program", [_padded_gather, _padded_gather_ordered],
                         ids=lambda p: p.__name__)
@pytest.mark.parametrize("rows,dim,positions,table_ops", [
    # products-sage.train-fused. The TPU keeps f32[N, 100] column-major, so
    # the program still transposes the whole table before it gathers rows
    # (PERF.md section 7): the one operation left that is not the gather
    (N, PRODUCTS["dim"], 1_081_344, ["parameter", "copy"]),
    # igb-small-sage.train-dedup: 1024 lanes, row-major as it is
    (1_000_000, 1024, 417_792, ["parameter"]),
])
def test_feature_gather_is_the_row_gather_alone_on_v5e(
        v5e, program, rows, dim, positions, table_ops):
    """No select over the gathered rows (mode="clip"): beside the gathers
    only index clamps and layout copies."""
    ops = compile_feature_gather(v5e, program, rows, dim, positions)
    table_shape, out_shape = f"f32[{rows},{dim}]", f"f32[{positions},{dim}]"
    assert [op for shape, op in ops if shape == table_shape] == table_ops, ops
    assert sum(shape == out_shape and op == "fusion" for shape, op in ops) == 1, ops
    assert {op for _, op in ops} <= {"parameter", "fusion", "copy", "copy-start",
                                     "copy-done"}, ops
    # a clamp and a gather for the table, the same again for `order`
    assert sum(op == "fusion" for _, op in ops) == (
        4 if program is _padded_gather_ordered else 2), ops


if __name__ == "__main__":
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name, fn in (
        ("fused train step, batch 1024",
         lambda: compile_train_step(desc, None, PRODUCTS["batch"])),
        ("dedup train step, batch 1024",
         lambda: compile_train_step(desc, DEDUP_CAPS, PRODUCTS["batch"])),
        ("serve bucket 64", lambda: compile_serve_bucket(desc)[0]),
        ("serve bucket 1", lambda: compile_serve_bucket(desc, 1)[0]),
        ("sharded-topology step at the products size, 4 devices",
         lambda: compile_sharded_topo_step(desc)),
        ("sharded-topology step at the products size, 1 device (the twin)",
         lambda: compile_sharded_topo_step(desc, 1)),
        ("sharded-feature step, 4 devices",
         lambda: compile_sharded_feature_step(desc, 4)),
        ("flat sharded-topology step at the papers100M cell's size, 4 devices",
         lambda: compile_flat_sharded_topo_step_at_papers_size(desc)[0]),
        ("igb model step, caps at margin 1.1: temporaries GiB",
         lambda: compile_igb_model_step(desc, IGB_CAPS)[1] / 2**30),
        ("igb model step, caps at margin 1.2: temporaries GiB",
         lambda: compile_igb_model_step(desc, IGB_CAPS_DEFAULT)[1] / 2**30),
        ("igb GAT step (the attention cell): temporaries GiB",
         lambda: compile_igb_gat_step(desc)[0].memory_analysis().temp_size_in_bytes / 2**30),
        *((f"sample_dense program, {cell}: temporaries GiB, compile seconds",
           lambda shape=shape: [(m.temp_size_in_bytes / 2**30, s) for _, m, s in
                                [compile_sample_dense_program(desc, **shape)]][0])
          for cell, shape in TRAIN_CELLS.items()),
    ):
        t0 = time.time()
        print(name, fn(), f"compiled in {time.time() - t0:.1f}s", flush=True)
