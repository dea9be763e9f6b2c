"""Cross-NUMA tensor parallelism (paper §3), executable in JAX.

The paper's §3.2 weight-partition plan, mapped 1:1 onto a mesh axis
(default ``"model"`` — the NUMA-node axis of the TPU adaptation):

* **row-partitioned** (output features split): ``w_q, w_k, w_v`` (split
  by attention head), ``w_gate, w_up``;
* **column-partitioned** (input features split): ``w_o, w_down``;
* everything else (norm gains, biases on the replicated dim) replicated.

§3.3's operators become:

* ``Scatter`` — entering a TP block.  Row-partitioned weights already
  live shard-locally, so the activation is *replicated* into every
  subgraph (a zero-copy view in the C++ engine; a no-op under
  shard_map because the input arrives replicated over the axis).
* ``Gather``  — leaving a TP block: sum the column-partitioned partial
  outputs — ``jax.lax.psum`` over the axis — and return to single-graph
  mode.

§3.4's synchronisation schedules:

* **Sync A** (global barrier after every operator): after each
  partitioned op the activation is all-gathered to full size and
  re-sliced, i.e. every node sees the globally coherent value before
  the next op.  This is the naive "global coherence" schedule and it
  costs one collective per op.
* **Sync B** (asynchronous subgraphs): activations stay shard-local for
  the whole block; the only collective is the Gather psum.  This is
  ArcLight's schedule.

Both schedules compute identical values (tested); they differ only in
collective traffic — Sync A's extra all-gathers are exactly the thread
idle time of Fig 9, measurable here as HLO collective bytes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


# ----------------------------------------------------------------------
# §3.2 — the weight-partition plan
# ----------------------------------------------------------------------

ROW_PARTITIONED = ("w_q", "w_k", "w_v", "w_gate", "w_up")
COL_PARTITIONED = ("w_o", "w_down")


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Paper §3.2 plan for one transformer layer's weights.

    Weight layout convention: every ``w_*`` is stored ``(d_in, d_out)``.
    Row-partitioning (by output feature / attention head) therefore
    shards axis 1; column-partitioning shards axis 0.
    """

    axis: str = "model"

    def spec_for(self, name: str) -> P:
        base = name.rsplit("/", 1)[-1]
        if base in ROW_PARTITIONED:
            return P(None, self.axis)
        if base in COL_PARTITIONED:
            return P(self.axis, None)
        if base in ("embed", "lm_head"):
            return P(None, self.axis)  # vocab-partitioned output features
        return P()  # norms, biases on replicated dims

    def params_specs(self, params: Dict[str, Any]) -> Dict[str, Any]:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: self.spec_for(
                "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                         for k in path)),
            params)


# ----------------------------------------------------------------------
# reference (non-TP) blocks — the "vanilla MLP" of Fig 8a
# ----------------------------------------------------------------------

def mlp_reference(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """Z = W_down^T · (silu(W_gate^T X) * (W_up^T X)), weights (in, out)."""
    y = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return y @ params["w_down"]


def attention_reference(params: Dict[str, jax.Array], x: jax.Array,
                        n_heads: int) -> jax.Array:
    """Single-sequence causal attention block (no cache), for TP checks."""
    t, d = x.shape
    hd = params["w_q"].shape[1] // n_heads
    q = (x @ params["w_q"]).reshape(t, n_heads, hd)
    k = (x @ params["w_k"]).reshape(t, n_heads, hd)
    v = (x @ params["w_v"]).reshape(t, n_heads, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(hd).astype(x.dtype)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(t, n_heads * hd) @ params["w_o"]


# ----------------------------------------------------------------------
# §3.3 / §3.4 — TP blocks under shard_map
# ----------------------------------------------------------------------

def _sync_a_coherce(x_local: jax.Array, axis: str, shard_dim: int,
                    ) -> jax.Array:
    """Sync A global barrier: all-gather the sharded activation so every
    node observes the coherent global value, then re-slice its shard.

    Numerically a no-op; in HLO it is an all-gather + dynamic-slice per
    call — the collective cost of per-op global synchronisation."""
    full = jax.lax.all_gather(x_local, axis, axis=shard_dim, tiled=True)
    n = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    size = full.shape[shard_dim] // n
    return jax.lax.dynamic_slice_in_dim(full, idx * size, size, shard_dim)


def mlp_tp(params: Dict[str, jax.Array], x: jax.Array, *, axis: str,
           sync_mode: str = "sync_b") -> jax.Array:
    """The paper's TP MLP (Fig 8b) as a shard_map body.

    Inputs: ``x`` replicated over ``axis`` (Scatter's activation view);
    ``w_gate, w_up`` row-sharded (axis 1), ``w_down`` col-sharded
    (axis 0).  Returns the replicated Z = Σ_i B_i Y_i (Gather).
    """
    y = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    if sync_mode == "sync_a":
        y = _sync_a_coherce(y, axis, shard_dim=y.ndim - 1)
    z_partial = y @ params["w_down"]
    return jax.lax.psum(z_partial, axis)          # Gather


def attention_tp(params: Dict[str, jax.Array], x: jax.Array, *,
                 n_heads: int, axis: str, sync_mode: str = "sync_b",
                 ) -> jax.Array:
    """Head-partitioned attention block (Fig 8c) as a shard_map body."""
    n_shards = jax.lax.psum(1, axis)
    heads_local = n_heads // n_shards
    t = x.shape[0]
    hd = params["w_q"].shape[1] // heads_local
    q = (x @ params["w_q"]).reshape(t, heads_local, hd)
    k = (x @ params["w_k"]).reshape(t, heads_local, hd)
    v = (x @ params["w_v"]).reshape(t, heads_local, hd)
    if sync_mode == "sync_a":
        q = _sync_a_coherce(q, axis, shard_dim=1)
        k = _sync_a_coherce(k, axis, shard_dim=1)
        v = _sync_a_coherce(v, axis, shard_dim=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(hd).astype(x.dtype)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    z_partial = o.reshape(t, heads_local * hd) @ params["w_o"]
    return jax.lax.psum(z_partial, axis)          # Gather


def make_tp_block(mesh: Mesh, kind: str, *, axis: str = "model",
                  sync_mode: str = "sync_b", n_heads: Optional[int] = None,
                  ) -> Callable[..., jax.Array]:
    """Wrap a TP block body in shard_map with the §3.2 weight specs.

    The returned callable takes (params, x) with *global* arrays; the
    shard_map in_specs implement Scatter (weights shard-local,
    activation replicated) and the psum inside implements Gather.
    """
    plan = PartitionPlan(axis)
    if kind == "mlp":
        body = functools.partial(mlp_tp, axis=axis, sync_mode=sync_mode)
        wnames = ("w_gate", "w_up", "w_down")
    elif kind == "attention":
        if n_heads is None:
            raise ValueError("attention block needs n_heads")
        body = functools.partial(attention_tp, n_heads=n_heads, axis=axis,
                                 sync_mode=sync_mode)
        wnames = ("w_q", "w_k", "w_v", "w_o")
    else:
        raise ValueError(f"unknown TP block kind {kind!r}")

    in_specs = ({w: plan.spec_for(w) for w in wnames}, P())
    return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)


# ----------------------------------------------------------------------
# engine-level helpers
# ----------------------------------------------------------------------

def shard_params(params: Dict[str, Any], mesh: Mesh,
                 plan: Optional[PartitionPlan] = None) -> Dict[str, Any]:
    """Bind every weight to its node-local pool (NamedSharding)."""
    plan = plan or PartitionPlan()
    specs = plan.params_specs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def collective_ops_in(fn: Callable[..., Any], *args: Any) -> Dict[str, int]:
    """Count collective primitives in the jaxpr of ``fn`` (cheap probe
    used by tests/benchmarks to compare Sync A vs Sync B)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    counts: Dict[str, int] = {}

    def walk(jx) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in ("psum", "all_gather", "all_to_all", "ppermute",
                        "reduce_scatter", "psum_scatter",
                        "all_gather_invariant", "psum_invariant"):
                counts[name] = counts.get(name, 0) + 1
            for sub in eqn.params.values():
                for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                    if hasattr(s, "eqns"):          # raw Jaxpr
                        walk(s)
                    elif hasattr(s, "jaxpr"):       # ClosedJaxpr
                        walk(s.jaxpr)
    walk(jaxpr.jaxpr)
    return counts
