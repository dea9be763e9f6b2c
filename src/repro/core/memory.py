"""ArcLight memory manager (paper §2.3).

Responsibilities, mirroring the C++ engine:

* pre-allocate a memory **pool** per NUMA node at startup (vs the single
  UMA buffer of llama.cpp, Fig 3) and bind every tensor's data area to
  the pool of the node whose threads consume it;
* a **double-buffering** mechanism for activations (Fig 4): two
  activation buffers alternated on layer parity, so layer *i* writes
  buffer ``i % 2`` while reading buffer ``(i-1) % 2`` — runtime
  activation memory is 2 × the per-layer peak instead of graph-lifetime
  liveness.

On TPU the "pool" is HBM of a mesh shard and binding is a
``NamedSharding``; this module is the *planner* that decides, before any
allocation, which pool each tensor lives in and how big each pool must
be.  The planner is exact enough to reproduce the paper's memory
accounting and is unit/property-tested (allocation never overlaps, peak
is minimal under the parity policy, UMA vs NUMA placement bytes match).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from .tensor import OpType, TensorHeader


_ALIGN = 128  # byte alignment of every carve-out (TPU lane/ sublane friendly)


def _align(n: int, a: int = _ALIGN) -> int:
    return (n + a - 1) // a * a


@dataclasses.dataclass
class Allocation:
    pool: str
    offset: int
    nbytes: int


@dataclasses.dataclass
class Pool:
    """A pre-allocated memory pool bound to one NUMA node (or UMA)."""

    name: str
    node_id: Optional[int]  # None = UMA / replicated
    cursor: int = 0
    peak: int = 0
    allocations: Dict[str, Allocation] = dataclasses.field(default_factory=dict)

    def alloc(self, name: str, nbytes: int) -> Allocation:
        a = Allocation(self.name, self.cursor, _align(nbytes))
        self.cursor += a.nbytes
        self.peak = max(self.peak, self.cursor)
        self.allocations[name] = a
        return a

    def reset(self) -> None:
        self.cursor = 0


class MemoryManager:
    """Plans weight + activation placement over per-node pools.

    ``numa=True``  -> one weight pool and one activation double-buffer
    pair per node (ArcLight strategy, Fig 3 bottom).
    ``numa=False`` -> a single monolithic buffer whose pages the OS
    interleaves (llama.cpp UMA strategy, Fig 3 top); modelled as one
    pool with ``node_id=None``.
    """

    def __init__(self, n_nodes: int = 1, *, numa: bool = True,
                 double_buffer: bool = True) -> None:
        if n_nodes < 1:
            raise ValueError("n_nodes must be >= 1")
        self.n_nodes = n_nodes
        self.numa = numa and n_nodes > 1
        self.double_buffer = double_buffer
        self.weight_pools: List[Pool] = []
        self.act_pools: List[List[Pool]] = []  # [node][parity]
        if self.numa:
            for i in range(n_nodes):
                self.weight_pools.append(Pool(f"weights/node{i}", i))
                self.act_pools.append(
                    [Pool(f"acts/node{i}/buf{p}", i) for p in range(2)])
        else:
            self.weight_pools.append(Pool("weights/uma", None))
            self.act_pools.append(
                [Pool(f"acts/uma/buf{p}", None) for p in range(2)])

    # ------------------------------------------------------------------
    def place_weight(self, h: TensorHeader) -> Allocation:
        """Bind a weight tensor to its node-local pool."""
        if not h.is_weight():
            raise ValueError(f"{h.name} is not a weight")
        pool = self._pool_for(h.node_id, kind="weight")
        a = pool.alloc(h.name, h.nbytes())
        h.node_id = pool.node_id if pool.node_id is not None else h.node_id
        h.buffer = (a.pool, a.offset)
        return a

    def _pool_for(self, node_id: Optional[int], *, kind: str,
                  parity: int = 0) -> Pool:
        idx = 0
        if self.numa:
            idx = 0 if node_id is None else node_id % self.n_nodes
        if kind == "weight":
            return self.weight_pools[idx]
        return self.act_pools[idx][parity % 2]

    # ------------------------------------------------------------------
    def plan_activations(self, layer_tensors: Sequence[Sequence[TensorHeader]],
                         ) -> Dict[str, Allocation]:
        """Double-buffered activation plan (Fig 4).

        ``layer_tensors[i]`` lists the activation headers produced by
        layer ``i``.  Layer parity selects the buffer; each buffer's
        cursor resets when its parity comes round again, which is safe
        because layer ``i+2`` never reads layer ``i``'s outputs in a
        standard layerwise forward pass.  Without double buffering the
        plan degenerates to one linear region (llama.cpp-style graph
        arena), whose peak we also report for comparison.
        """
        plan: Dict[str, Allocation] = {}
        if not self.double_buffer:
            for layer in layer_tensors:
                for h in layer:
                    pool = self._pool_for(h.node_id, kind="act", parity=0)
                    plan[h.name] = pool.alloc(h.name, h.nbytes())
            return plan

        for i, layer in enumerate(layer_tensors):
            parity = i % 2
            # reset every pool of this parity: the previous same-parity
            # layer's activations are dead once the next layer ran.
            for node_pools in self.act_pools:
                node_pools[parity].reset()
            for h in layer:
                if h.op in (OpType.WEIGHT,):
                    raise ValueError(f"weight {h.name} in activation plan")
                pool = self._pool_for(h.node_id, kind="act", parity=parity)
                plan[h.name] = pool.alloc(h.name, h.nbytes())
                h.buffer = (plan[h.name].pool, plan[h.name].offset)
        return plan

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------
    def weight_bytes(self) -> Dict[str, int]:
        return {p.name: p.peak for p in self.weight_pools}

    def activation_bytes(self) -> Dict[str, int]:
        return {p.name: p.peak for pools in self.act_pools for p in pools}

    def total_bytes(self) -> int:
        return (sum(self.weight_bytes().values())
                + sum(self.activation_bytes().values()))

    def per_node_bytes(self) -> Dict[int, int]:
        """Bytes resident in each node's local memory."""
        out: Dict[int, int] = {}
        for p in self.weight_pools:
            out[p.node_id or 0] = out.get(p.node_id or 0, 0) + p.peak
        for pools in self.act_pools:
            for p in pools:
                out[p.node_id or 0] = out.get(p.node_id or 0, 0) + p.peak
        return out


def plan_graph_memory(order: Sequence[TensorHeader], n_nodes: int, *,
                      numa: bool, double_buffer: bool,
                      layer_of: Optional[Dict[int, int]] = None,
                      ) -> MemoryManager:
    """Convenience: place a whole ForwardGraph execution list.

    ``layer_of`` maps ``id(header) -> layer index`` for the parity
    policy; when absent, every node is treated as layer 0 (single
    buffer).
    """
    mm = MemoryManager(n_nodes, numa=numa, double_buffer=double_buffer)
    acts_by_layer: Dict[int, List[TensorHeader]] = {}
    for h in order:
        if h.is_weight():
            mm.place_weight(h)
            continue
        layer = (layer_of or {}).get(id(h), 0)
        acts_by_layer.setdefault(layer, []).append(h)
    layers = [acts_by_layer[k] for k in sorted(acts_by_layer)]
    mm.plan_activations(layers)
    return mm
