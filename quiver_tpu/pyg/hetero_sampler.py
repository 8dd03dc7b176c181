"""Typed-edge k-hop sampler: node types, relations between them, and a
frontier kept per node type.

A heterogeneous graph is a set of node types, each with its own local ids
``0..n_t-1``, and relations ``(src, name, dst)``, each a CSR indexed by the
DESTINATION type's local id whose entries are SOURCE-type local ids: the
in-neighbours a message of that relation comes from (`HeteroCSRTopo`).

`HeteroSampler.sample_dense` draws, hop by hop, ``k`` in-neighbours per
relation for every frontier node of that relation's destination type (the
relational neighbour sampling of PyG's ``NeighborLoader`` on a
``HeteroData``, and of the R-GAT/R-GCN reference trainers). The frontier is
laid out per node type in static segments:

- hop ``l``'s segment of type ``t`` is the valid prefix of hop ``l+1``'s
  (the dedup reindex keeps its seeds verbatim, `ops.reindex`), so every
  relation's targets are the prefix of its destination type's segment, the
  `DenseAdj` contract of the single-relation sampler kept per type;
- a type's next segment is ONE `ops.reindex.segment_reindex` over the draws
  of every relation whose source it is, capped at a static per-(hop, type)
  width (`calibrate_caps`);
- a relation samples only its destination type's segment, through the same
  one-hop ops as `GraphSageSampler` (`sage_sampler.one_hop_binder`, tiled or
  flat per relation).

All of it is ONE device program a call (`hetero_sample_program`), as
`sage_sampler.sample_dense_program` is for one relation.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.reindex import segment_reindex
from ..shard_tensor import _device_of
from ..trace import observe, trace_enabled, trace_scope
from ..utils import CSRTopo
from .sage_sampler import DenseAdj, one_hop_binder

Relation = Tuple[str, str, str]  # (source type, name, destination type)


def relation_key(rel: Relation) -> str:
    """``"src__name__dst"``: a relation's name in the sample's and the
    model's dicts (PyG writes edge types the same way)."""
    return "__".join(rel)


class HeteroCSRTopo:
    """Node types and typed relations (`CSRTopo` per relation).

    ``num_nodes`` maps each node type to its node count, in the order global
    ids are laid out (type ``t``'s node ``i`` is global row ``offsets[t] +
    i`` of a feature table of every type's rows one after the other).
    ``relations`` maps ``(src, name, dst)`` to a `CSRTopo` whose row ``i`` is
    destination node ``i`` and whose entries are its source-type
    in-neighbours."""

    def __init__(self, num_nodes: Mapping[str, int], relations: Mapping[Relation, CSRTopo]):
        self.node_types = tuple(num_nodes)
        self.num_nodes = {t: int(n) for t, n in num_nodes.items()}
        starts = np.concatenate([[0], np.cumsum([self.num_nodes[t] for t in self.node_types])])
        self.offsets = {t: int(o) for t, o in zip(self.node_types, starts)}
        self.total_nodes = int(starts[-1])
        self.relations = tuple(tuple(r) for r in relations)
        self.csr = {tuple(r): topo for r, topo in relations.items()}
        for (src, name, dst), topo in self.csr.items():
            if any("__" in part for part in (src, name, dst)):
                raise ValueError(f"{(src, name, dst)}: '__' joins a relation's key")
            if src not in self.num_nodes or dst not in self.num_nodes:
                raise ValueError(f"relation {(src, name, dst)} names an unknown node type")
            if topo.node_count != self.num_nodes[dst]:
                raise ValueError(f"relation {relation_key((src, name, dst))}: {topo.node_count} "
                                 f"rows for {self.num_nodes[dst]} {dst} nodes")
            if topo.edge_count and int(topo.indices.max()) >= self.num_nodes[src]:
                raise ValueError(f"relation {relation_key((src, name, dst))}: an entry past "
                                 f"the {self.num_nodes[src]} {src} nodes")


@dataclass(frozen=True)
class HeteroBlocks:
    """What a relational model takes beside its input rows: the sampled
    blocks, outermost hop first, each a dict ``relation_key -> DenseAdj``
    (``cols`` into the source type's segment, targets the prefix of the
    destination type's), and ``segments``, the static ``(type, width)`` of
    the input rows' per-type segments, in the order the rows are stacked.
    A pytree whose segments are static."""

    layers: Tuple[Dict[str, DenseAdj], ...]
    segments: Tuple[Tuple[str, int], ...]

    def spans(self) -> Dict[str, Tuple[int, int]]:
        """``{type: (first row, width)}`` of the stacked input rows."""
        out, at = {}, 0
        for t, w in self.segments:
            out[t] = (at, w)
            at += w
        return out


jax.tree_util.register_dataclass(HeteroBlocks, data_fields=["layers"], meta_fields=["segments"])


class HeteroDenseSample(NamedTuple):
    n_id: jax.Array          # [sum of the last hop's widths] GLOBAL ids, types stacked
    batch_size: int
    adjs: HeteroBlocks       # what the model takes (`HeteroBlocks`)
    type_ids: Dict[str, jax.Array]     # [W_L[t]] local ids of the last hop, valid prefix
    type_counts: Dict[str, jax.Array]  # [L + 1] valid count of the type's segment a hop
    cap_overflow: jax.Array  # scalar int32: unique nodes the caps dropped
    overflow: jax.Array      # [L, T] of them by (hop, type)
    raw_counts: jax.Array    # [L, T] pre-cap unique counts (`calibrate_caps`)
    valid_slots: jax.Array   # scalar int32: sampled slots holding a neighbour


class SamplePlan(NamedTuple):
    """The static half of a typed sample (hashable: the program's static
    argument). ``caps[l][i]`` caps hop ``l + 1``'s segment of
    ``node_types[i]`` (None: uncapped); ``hops[j]`` is relation ``j``'s
    `one_hop_binder` triple."""

    node_types: Tuple[str, ...]
    num_nodes: Tuple[int, ...]
    relations: Tuple[Relation, ...]
    seed_type: str
    sizes: Tuple[int, ...]
    caps: Optional[Tuple[Tuple[Optional[int], ...], ...]]
    hops: Tuple[Tuple[str, bool, int], ...]

    def widths(self, batch: int) -> List[Dict[str, int]]:
        """Static segment widths per hop, ``[{type: width}]`` for hops
        0..L; a type no draw reaches yet has no segment. A type's next width
        is its own plus ``k`` per target of every relation it is the source
        of, capped, and never under its own (the prefix)."""
        out = [{self.seed_type: int(batch)}]
        for l, k in enumerate(self.sizes):
            cur, nxt = out[-1], {}
            for i, t in enumerate(self.node_types):
                own = cur.get(t, 0)
                w = own + sum(cur[d] * k for s, _, d in self.relations if s == t and d in cur)
                if w == 0:
                    continue
                cap = None if self.caps is None else self.caps[l][i]
                nxt[t] = w if cap is None else max(min(w, int(cap)), own)
            out.append(nxt)
        return out


def _segment(ids, count, width, dtype):
    if ids is None:
        return jnp.zeros((0,), dtype), jnp.zeros((0,), bool)
    return ids, jnp.arange(width, dtype=jnp.int32) < count


@functools.partial(jax.jit, static_argnames=("plan",))
def hetero_sample_program(key0, call, seeds, graphs, *, plan: SamplePlan) -> HeteroDenseSample:
    """`HeteroSampler.sample_dense`'s one program (XLA module
    ``jit_hetero_sample_program``): call ``call``'s key folded from the base
    key, then per hop every relation's draws over its destination type's
    segment and per node type one `segment_reindex` of what was drawn into
    it. ``graphs`` (one pytree per relation, `HeteroSampler.graph_arrays`)
    and ``key0`` are arguments, never constants."""
    key = jax.random.fold_in(key0, call)
    fns = [one_hop_binder(*hop)(g) for hop, g in zip(plan.hops, graphs)]
    widths = plan.widths(seeds.shape[0])
    n_types = len(plan.node_types)
    id_dtype = seeds.dtype
    # per type: (ids, valid count) of the current hop's segment
    front = {plan.seed_type: (seeds, jnp.asarray(seeds.shape[0], jnp.int32))}
    counts = {t: [] for t in plan.node_types}
    layers, raws, overflows = [], [], []
    valid_slots = jnp.asarray(0, jnp.int32)
    for l, k in enumerate(plan.sizes):
        key, sub = jax.random.split(key)
        for t in plan.node_types:
            counts[t].append(front[t][1] if t in front else jnp.asarray(0, jnp.int32))
        draws = {}
        for j, (src, _, dst) in enumerate(plan.relations):
            if dst in front:
                ids, valid = _segment(*front[dst], widths[l][dst], id_dtype)
                draws[j] = fns[j](ids, valid, k, jax.random.fold_in(sub, j))
        adjs, nxt = {}, {}
        for i, t in enumerate(plan.node_types):
            if t not in widths[l + 1]:
                raws.append(jnp.asarray(0, jnp.int32))
                overflows.append(jnp.asarray(0, jnp.int32))
                continue
            into = [j for j in draws if plan.relations[j][0] == t]
            ids, valid = _segment(*front.get(t, (None, 0)), widths[l].get(t, 0), id_dtype)
            flat = jnp.concatenate([draws[j][0].astype(id_dtype).T.reshape(-1) for j in into])
            flat_valid = jnp.concatenate([draws[j][1].T.reshape(-1) for j in into])
            n_id, count, local = segment_reindex(ids, valid, flat, flat_valid)
            raws.append(count)
            cap = widths[l + 1][t]
            over = jnp.maximum(count - cap, 0)
            overflows.append(over)
            n_id, count = n_id[:cap], jnp.minimum(count, cap)
            at = 0
            for j in into:
                nbrs, drawn = draws[j]
                w, kk = nbrs.shape
                cols = local[at: at + w * kk].reshape(kk, w).T
                at += w * kk
                mask = drawn & (cols < cap)
                valid_slots = valid_slots + mask.sum().astype(jnp.int32)
                adjs[relation_key(plan.relations[j])] = DenseAdj(
                    cols=cols, mask=mask, n_src=count, n_dst=front[plan.relations[j][2]][1])
            nxt[t] = (n_id, count)
        layers.append(adjs)
        front = nxt
    last = widths[-1]
    type_ids = {t: front[t][0] for t in plan.node_types if t in last}
    for t in plan.node_types:
        counts[t].append(front[t][1] if t in front else jnp.asarray(0, jnp.int32))
    offsets = np.concatenate([[0], np.cumsum(plan.num_nodes)])
    # global rows of the stacked table; a padded lane reads its type's last row
    n_id = jnp.concatenate([
        jnp.minimum(type_ids[t], plan.num_nodes[i] - 1).astype(jnp.int32) + int(offsets[i])
        for i, t in enumerate(plan.node_types) if t in last])
    overflow = jnp.stack(overflows).reshape(len(plan.sizes), n_types)
    return HeteroDenseSample(
        n_id=n_id,
        batch_size=None,
        adjs=HeteroBlocks(layers=tuple(layers[::-1]),
                          segments=tuple((t, last[t]) for t in plan.node_types if t in last)),
        type_ids=type_ids,
        type_counts={t: jnp.stack(c) for t, c in counts.items()},
        cap_overflow=overflow.sum(),
        overflow=overflow,
        raw_counts=jnp.stack(raws).reshape(len(plan.sizes), n_types),
        valid_slots=valid_slots,
    )


class HeteroSampler:
    """K-hop sampler over a `HeteroCSRTopo`, TPU mode (the graph in HBM).

    Parameters
    ----------
    topo : HeteroCSRTopo
    sizes : fan-out per hop, outermost first as in `GraphSageSampler`; each
        relation draws that many in-neighbours of each frontier node of its
        destination type
    caps : optional static widths of each hop's segments, ``{type: [cap of
        hop 1, ..., cap of hop L]}`` (`calibrate_caps`); a type not named is
        uncapped
    seed : RNG seed; sampling is deterministic given (seed, call index)
    seed_type : the node type of the seeds (default: the first type)
    layout : ``"tiled"`` | ``"flat"`` for every relation, or a dict
        ``relation_key -> layout`` (`GraphSageSampler`'s ``layout``: tiles
        cost 128 lanes a node, so a sparse relation is cheaper flat)

    TPU mode, uniform draws and dedup only: no host engine, no fused
    (no-dedup) layout, no streaming or temporal binding.
    """

    def __init__(self, topo: HeteroCSRTopo, sizes: Sequence[int],
                 caps: Optional[Mapping[str, Sequence[int]]] = None, seed: int = 0,
                 device=0, seed_type: Optional[str] = None,
                 layout: Union[str, Mapping[str, str]] = "tiled"):
        self.topo = topo
        self.sizes = tuple(int(k) for k in sizes)
        self.seed_type = seed_type or topo.node_types[0]
        self.device = device
        keys = [relation_key(r) for r in topo.relations]
        layouts = {k: layout for k in keys} if isinstance(layout, str) else dict(layout)
        for k in keys:
            if layouts.get(k, "tiled") not in ("tiled", "flat"):
                raise ValueError(f"unsupported layout for {k}: {layouts[k]}")
        self.layouts = {k: layouts.get(k, "tiled") for k in keys}
        self.caps = None
        if caps is not None:
            self.set_caps(caps)
        self._key0 = jax.random.key(seed)
        self._call = 0
        self._graphs = None
        self._pending = None  # the last call's counters, recorded at the next call

    def set_caps(self, caps: Mapping[str, Sequence[int]]) -> None:
        unknown = set(caps) - set(self.topo.node_types)
        if unknown:
            raise ValueError(f"caps name unknown node types: {sorted(unknown)}")
        for t, c in caps.items():
            if len(c) != len(self.sizes):
                raise ValueError(f"caps of {t}: {len(c)} hops for {len(self.sizes)}")
        self.caps = {t: tuple(int(v) for v in c) for t, c in caps.items()}

    def plan(self) -> SamplePlan:
        caps = None if self.caps is None else tuple(
            tuple(self.caps[t][l] if t in self.caps else None for t in self.topo.node_types)
            for l in range(len(self.sizes)))
        return SamplePlan(
            node_types=self.topo.node_types,
            num_nodes=tuple(self.topo.num_nodes[t] for t in self.topo.node_types),
            relations=self.topo.relations, seed_type=self.seed_type, sizes=self.sizes,
            caps=caps,
            hops=tuple((self.layouts[relation_key(r)], False, 0)  # uniform: no max_deg
                       for r in self.topo.relations))

    def graph_arrays(self) -> Tuple:
        """Each relation's device arrays, in relation order, placed once:
        `CSRTopo.to_device_tiled` or `CSRTopo.to_device_lane_rows`."""
        if self._graphs is None:
            dev = _device_of(self.device) if isinstance(self.device, int) else None
            self._graphs = tuple(
                topo.to_device_tiled(dev) if self.layouts[relation_key(r)] == "tiled"
                else topo.to_device_lane_rows(dev)
                for r, topo in self.topo.csr.items())
        return self._graphs

    def _run(self, seeds, plan: SamplePlan) -> HeteroDenseSample:
        seeds = np.asarray(seeds).astype(np.int32, copy=False)
        call, self._call = self._call, self._call + 1
        ds = hetero_sample_program(self._key0, np.uint32(call), seeds, self.graph_arrays(),
                                   plan=plan)
        return ds._replace(batch_size=seeds.shape[0])

    def sample_dense(self, seeds) -> HeteroDenseSample:
        """Sample a padded, jittable typed mini-batch: ONE program a call
        (call i draws ``fold_in(key(seed), i)``). The ``quiver.sample`` span
        (`GraphSageSampler.sample_dense`'s, read by the same metric) carries
        ``call`` and ``compiled``; ``quiver.sample.hetero`` nests inside it
        and names the typed call. While tracing is on, the
        counters of the PREVIOUS call are recorded here (its scalars are
        ready by then, so no call waits on its own program): sampled slots
        and those holding a neighbour, summed over relations and hops, their
        share, and each (hop, type)'s cap overflow."""
        with trace_scope("quiver.sample", call=self._call) as span, \
                trace_scope("quiver.sample.hetero"):
            if trace_enabled():
                self._record_pending()
            built = hetero_sample_program._cache_size()
            plan = self.plan()
            ds = self._run(seeds, plan)
            span.set(compiled=int(hetero_sample_program._cache_size() > built))
            if trace_enabled():
                self._pending = (plan, ds.batch_size, ds.valid_slots, ds.overflow)
        return ds

    def _record_pending(self) -> None:
        if self._pending is None:
            return
        plan, batch, valid, overflow = self._pending
        self._pending = None
        widths = plan.widths(batch)
        slots = sum(widths[l][d] * k for l, k in enumerate(plan.sizes)
                    for _, _, d in plan.relations if d in widths[l])
        valid = int(valid)
        observe("quiver.sample.hetero.slots", slots)
        observe("quiver.sample.hetero.valid_slots", valid)
        observe("quiver.sample.hetero.valid_slot_pct", 100.0 * valid / slots)
        overflow = np.asarray(overflow)
        for l in range(len(plan.sizes)):
            for i, t in enumerate(plan.node_types):
                if t in widths[l + 1]:
                    observe(f"quiver.sample.hetero.cap_overflow.h{l + 1}.{t}", int(overflow[l, i]))

    def calibrate_caps(self, probe_seeds, margin: float = 1.2,
                       granule: int = 4096) -> Dict[str, Tuple[int, ...]]:
        """Per-(hop, type) caps from uncapped samples of ``probe_seeds``
        (``[m, B]``, m >= 8 for a stable max), as
        `GraphSageSampler.calibrate_caps` sets per-hop caps: each type's
        largest unique count at each hop times ``margin``, rounded up to
        ``granule``, clipped to the uncapped width (`caps_from_counts`);
        installs them on this sampler."""
        batches = np.stack([np.asarray(b) for b in probe_seeds])
        if batches.ndim != 2:
            raise ValueError(f"probe_seeds must be [m, B]; got {batches.shape}")
        uncapped = self.plan()._replace(caps=None)
        raw = np.stack([np.asarray(self._run(b, uncapped).raw_counts) for b in batches])
        widths = uncapped.widths(batches.shape[1])
        caps = {}
        for i, t in enumerate(self.topo.node_types):
            need = raw[:, :, i].max(axis=0) * margin
            caps[t] = tuple(int(min(-(-n // granule) * granule, widths[l + 1].get(t, 0)))
                            for l, n in enumerate(need))
        self.set_caps(caps)
        return caps
