"""Sharding policy: the paper's §3.2 partition plan on the mesh.

Weight rules (name-driven, rank-aware — stacked layers carry a leading
L axis that stays unsharded unless FSDP is active):

    row-partitioned  (d_in, d_out): w_q w_k w_v w_gate w_up w_y w_x
                                    in_proj           -> P(fsdp, "model")
    col-partitioned  (d_in, d_out): w_o w_down w_out out_proj
                                    -> P("model", fsdp)
    vocab-partitioned: embed (V, d) -> P("model", fsdp);
                       lm_head (d, V) -> P(fsdp, "model")
    MoE experts (E, d, f): baseline TP inside every expert
                       w_gate/w_up -> P(ep, fsdp, "model"),
                       w_down (E, f, d) -> P(ep, "model", fsdp)
                       (ep = "model"-sharded expert axis in the
                       expert-parallel variant, None in baseline)
    everything else (norm gains, biases, A_log, conv, router): replicated

``fsdp`` is the "data" axis for the big archs (those with remat=True),
else None — the capacity analogue of ArcLight's per-node pools.

Activation rules: batch over ("pod","data"); KV caches shard batch over
"data" and head_dim over "model"; long_500k (batch=1) shards the cache
*sequence* over "data" instead.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig


ROW_NAMES = ("w_q", "w_k", "w_v", "w_gate", "w_up", "w_y", "w_x",
             "in_proj")
COL_NAMES = ("w_o", "w_down", "w_out", "out_proj")


@dataclasses.dataclass(frozen=True)
class Policy:
    """Knobs the perf hillclimb sweeps (EXPERIMENTS.md §Perf)."""

    fsdp: bool = True               # shard big-arch params over "data"
    fsdp_threshold: float = 2e10    # params above this get FSDP
    expert_parallel: bool = False   # experts over "model" (vs TP inside)
    seq_shard_cache: bool = True    # long_500k: cache seq over "data"
    shard_cache_head_dim: bool = True
    microbatches: int = 1           # gradient accumulation (train)
    head_aligned: bool = True       # replicate attn weights when Hq
                                    # doesn't divide the model axis
                                    # (§3.2 "partitioned by attention
                                    # heads"); disabled for prefill

    def fsdp_active(self, cfg: ModelConfig) -> bool:
        return self.fsdp and cfg.param_count() > self.fsdp_threshold


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_spec(cfg: ModelConfig, path: str, ndim: int, shape, mesh: Mesh,
               policy: Policy, *, use_time: bool = False) -> P:
    """``use_time=True`` drops the FSDP axis — the spec a weight must
    have at its point of use (the per-layer unshard constraint that
    makes GSPMD all-gather the WEIGHTS, never the activation batch)."""
    name = path.split("/")[-1]
    fsdp = ("data" if (not use_time and policy.fsdp_active(cfg)
                       and "data" in mesh.axis_names) else None)
    n_model = mesh.shape.get("model", 1)
    n_data = mesh.shape.get("data", 1)

    def ok(dim_size: int, axis: Optional[str]) -> Optional[str]:
        if axis is None:
            return None
        n = mesh.shape.get(axis, 1)
        return axis if dim_size % n == 0 else None

    # expert tensors (E, d, f) / (E, f, d)
    if ndim == 3 + ("layers/" in path and cfg.uniform) and name in (
            "w_gate", "w_up", "w_down") and "moe" in path:
        # strip optional leading L: operate on the last 3 dims
        lead = ndim - 3
        E, a, b = shape[lead:]
        ep = "model" if (policy.expert_parallel and E % n_model == 0) \
            else None
        if ep:  # expert-parallel: whole experts per shard
            spec = [None] * lead + [ep, None, None]
            if fsdp:
                spec[lead + 1] = ok(a, fsdp)
            return P(*spec)
        if name == "w_down":   # (E, f, d): f is the contracted/sharded dim
            return P(*([None] * lead + [None, ok(a, "model"),
                                        ok(b, fsdp)]))
        return P(*([None] * lead + [None, ok(a, fsdp), ok(b, "model")]))

    if ndim >= 2:
        lead = ndim - 2
        a, b = shape[lead:]
        if name == "embed":
            return P(ok(a, "model"), ok(b, fsdp))
        if name == "lm_head":
            return P(ok(a, fsdp), ok(b, "model"))
        # paper §3.2: "W_q, W_k, W_v are partitioned BY ATTENTION HEADS"
        # — when the *query* heads don't divide the model axis (gemma3:
        # 4 heads / 16 shards) GSPMD must gather mid-softmax; replicate
        # the whole attention block instead (MLP still TP).  Archs with
        # divisible Hq keep the standard split (replicating only K/V
        # breaks the GQA reshape sharding — measured, EXPERIMENTS W1b).
        attn_names = ("w_q", "w_k", "w_v", "w_o")
        if (name in attn_names and "attn" in path and policy.head_aligned
                and cfg.n_heads % n_model):
            if name == "w_o":
                return P(*([None] * lead + [None, ok(b, fsdp)]))
            return P(*([None] * lead + [ok(a, fsdp), None]))
        if name in ROW_NAMES:
            return P(*([None] * lead + [ok(a, fsdp), ok(b, "model")]))
        if name in COL_NAMES:
            return P(*([None] * lead + [ok(a, "model"), ok(b, fsdp)]))
    return P()


def params_shardings(cfg: ModelConfig, params_shapes: Any, mesh: Mesh,
                     policy: Policy) -> Any:
    def f(path, leaf):
        spec = param_spec(cfg, _path_str(path), leaf.ndim, leaf.shape,
                          mesh, policy)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(f, params_shapes)


def make_layer_constraint(cfg: ModelConfig, mesh: Mesh, policy: Policy):
    """Per-layer weight unshard constraint for FSDP archs (see
    ``param_spec(use_time=True)``); None when FSDP is off."""
    if not policy.fsdp_active(cfg) or "data" not in mesh.axis_names:
        return None

    def constrain(layer_params):
        def f(path, leaf):
            spec = param_spec(cfg, _path_str(path), leaf.ndim, leaf.shape,
                              mesh, policy, use_time=True)
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec))
        return jax.tree_util.tree_map_with_path(f, layer_params)

    return constrain


def make_moe_hook(cfg: ModelConfig, mesh: Mesh, policy: Policy, *,
                  batch_size: int):
    """Run MoE dispatch inside shard_map over the data axis.

    Under plain GSPMD the capacity-buffer scatter uses *global* token
    indices, which the solver can only honour by replicating the
    (E, C, d) buffers and all-reducing them — ~10 TB of collectives per
    step for phi3.5 train_4k (measured; EXPERIMENTS.md §Perf).  Inside
    shard_map each data shard dispatches its own tokens with local
    indices (zero dispatch collectives), expert FFNs run TP over
    ``model`` (w_up/w_gate row-sharded on f, w_down col-sharded), and
    one psum per block implements the paper's Gather.

    This is exactly ArcLight's Scatter/Gather applied to experts: the
    thread-group (= data-shard) owns its tokens, the node-local weights
    (= f-slices) never move, synchronisation happens once per block.
    """
    if not cfg.n_experts:
        return None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if not dp or batch_size % n_dp:
        return None
    n_model = mesh.shape.get("model", 1)
    if cfg.d_ff % n_model:
        return None
    from jax import shard_map
    from ..models.moe import moe as moe_fn

    ep = policy.expert_parallel and cfg.n_experts % n_model == 0
    if ep:
        w_specs = {"router": P(), "w_gate": P("model", None, None),
                   "w_up": P("model", None, None),
                   "w_down": P("model", None, None)}
    else:
        w_specs = {"router": P(), "w_gate": P(None, None, "model"),
                   "w_up": P(None, None, "model"),
                   "w_down": P(None, "model", None)}
    if cfg.act != "silu":
        w_specs.pop("w_gate")
    x_spec = P(dp, None, None)

    def body(mp, x):
        if ep:
            y, aux = _moe_expert_parallel(
                mp, x, k=cfg.experts_per_token, act=cfg.act,
                capacity_factor=cfg.capacity_factor, axis="model")
        else:
            y, aux = moe_fn(mp, x, k=cfg.experts_per_token, act=cfg.act,
                            impl="scatter",
                            capacity_factor=cfg.capacity_factor)
            y = jax.lax.psum(y, "model")          # Gather (§3.3)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    def hook(moe_params, x):
        return shard_map(body, mesh=mesh, in_specs=(w_specs, x_spec),
                         out_specs=(x_spec, P()), check_vma=False)(
                             moe_params, x)

    return hook


def _moe_expert_parallel(mp, x, *, k: int, act: str,
                         capacity_factor: float, axis: str):
    """Expert-parallel dispatch: each ``axis`` (model) shard owns
    E/n whole experts at FULL width (no f-split, better MXU shapes);
    tokens are replicated over ``axis`` inside the data shard, so each
    shard slices its experts\' capacity rows, runs them, and one psum
    over ``axis`` merges the combine (the optimized §Perf variant —
    trades ~k*capacity_factor x psum bytes for unsplit expert GEMMs).
    """
    import jax.numpy as jnp
    n = jax.lax.psum(1, axis)
    m_idx = jax.lax.axis_index(axis)
    E_local = mp["w_up"].shape[0]
    E = E_local * n
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2d = x.reshape(-1, d)
    T = x2d.shape[0]
    logits = (x2d.astype(jnp.float32) @ mp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    cap = max(int(T * k / E * capacity_factor), k)
    e_flat = topi.reshape(-1)
    w_flat = topv.reshape(-1).astype(x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = jnp.sum(pos * onehot, axis=-1)
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_flat * cap + pos_in_e, E * cap)
    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    buf = buf.at[slot].add(x2d[tok_idx] * keep[:, None].astype(x.dtype))
    buf = buf[:-1].reshape(E, cap, d)

    mine = jax.lax.dynamic_slice_in_dim(buf, m_idx * E_local, E_local, 0)
    up = jnp.einsum("ecd,edf->ecf", mine, mp["w_up"])
    if act == "silu":
        up = jax.nn.silu(jnp.einsum("ecd,edf->ecf", mine,
                                    mp["w_gate"])) * up
    out_mine = jnp.einsum("ecf,efd->ecd", up, mp["w_down"])

    out = jnp.zeros((E, cap, d), x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, out_mine,
                                              m_idx * E_local, 0)
    out = jax.lax.psum(out, axis)                       # Gather (§3.3)
    out = jnp.concatenate([out.reshape(E * cap, d),
                           jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = out[jnp.where(keep, slot, E * cap)] \
        * keep[:, None].astype(x.dtype)
    y2d = jnp.zeros_like(x2d).at[tok_idx].add(gathered * w_flat[:, None])
    assign = jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(jnp.mean(assign, axis=0) * jnp.mean(probs, axis=0))
    return y2d.reshape(*lead, d), aux


def seq_shard_axes(mesh: Mesh, batch_size: int, cache_len: int,
                   n_kv_heads: int):
    """Tiered cache-sequence sharding decision, shared by the cache
    specs and the decode hook so they can never diverge.

    Returns (axes, batch_sharded): the axes the cache sequence shards
    over — ("model",) for batch-sharded caches, up to data x model for
    long-context batch=1 — or () when whole-kv-head sharding is free
    (no collective at all) or local slices would drop below one
    512-slot attention chunk (merge overhead beats locality; rg-2b
    measured)."""
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    batch_sharded = batch_size % max(n_data, 1) == 0 and n_data > 1
    if batch_sharded:
        if n_kv_heads % max(n_model, 1) == 0:
            return (), True
        if (n_model > 1 and cache_len % n_model == 0
                and cache_len // n_model >= 512):
            return ("model",), True
        return (), True
    for cand in (("data", "model"), ("data",), ("model",)):
        axes = tuple(a for a in cand if mesh.shape.get(a, 1) > 1)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if axes and n > 1 and cache_len % n == 0 and cache_len // n >= 512:
            return axes, False
    return (), False


def make_decode_attn_hook(cfg: ModelConfig, mesh: Mesh, policy: Policy, *,
                          batch_size: int, cache_len: int):
    """Sequence-sharded flash-decoding with fully-local cache updates.

    The KV cache's sequence axis shards over "model" (batch-sharded
    caches) or over data x model (long-context, batch=1).  Under plain
    GSPMD the attention chunk-scan is sequential, so the solver either
    all-gathers the cache every token or head_dim-shards it and psums
    every score chunk (both measured; EXPERIMENTS §Perf).  This hook is
    the paper's Scatter/Gather applied to the cache sequence:

    * write: the one new KV lands on the single shard that owns its
      ring slot (a masked dynamic_update_slice — no resharding at all);
    * attend: every shard runs blockwise attention over its local slice
      (un-normalised partials);
    * Gather: one LSE-weighted psum (``combine_partials``).
    """
    if not policy.seq_shard_cache:
        return None
    seq_axes, batch_sharded = seq_shard_axes(mesh, batch_size, cache_len,
                                             cfg.n_kv_heads)
    if not seq_axes:
        return None
    bspec = (tuple(a for a in ("pod", "data") if a in mesh.axis_names)
             if batch_sharded else None)
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    from jax import shard_map
    from ..models.attention import combine_partials, flash_attention

    local = cache_len // n_shards

    def body(q, kn, vn, ck, cv, cp, window, pos):
        idx = jax.lax.axis_index(seq_axes[0])
        for a in seq_axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        import jax.numpy as jnp
        slot = pos % cache_len
        local_slot = slot - idx * local
        own = (local_slot >= 0) & (local_slot < local)
        safe = jnp.clip(local_slot, 0, local - 1)
        ck_new = jax.lax.dynamic_update_slice_in_dim(ck, kn, safe, 1)
        cv_new = jax.lax.dynamic_update_slice_in_dim(cv, vn, safe, 1)
        ck = jnp.where(own, ck_new, ck)
        cv = jnp.where(own, cv_new, cv)
        cp = jax.lax.dynamic_update_slice(cp, pos[None], (slot,))
        p_local = jax.lax.dynamic_slice(cp, (idx * local,), (local,))
        part = flash_attention(
            q, ck, cv, causal=True, window=window, q_offset=pos,
            kv_positions=p_local, chunk=min(512, local),
            return_partial=True, softcap=cfg.attn_logit_softcap,
            scale=cfg.attention_multiplier)
        out = combine_partials(part, seq_axes, q.dtype)
        return out, ck, cv, cp

    seq_dim_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    seq_spec = P(bspec, seq_dim_spec, None, None)
    q_spec = P(bspec, None, None, None)

    seq_ns = NamedSharding(mesh, seq_spec)

    def hook(q, kn, vn, ck, cv, cp, window, pos):
        # pin inputs/outputs so the surrounding scan cannot pick a
        # different (e.g. head_dim-sharded) layout for its ys and
        # all-to-all the cache every layer
        ck = jax.lax.with_sharding_constraint(ck, seq_ns)
        cv = jax.lax.with_sharding_constraint(cv, seq_ns)
        out, ck, cv, cp = shard_map(
            body, mesh=mesh,
            in_specs=(q_spec, q_spec, q_spec, seq_spec, seq_spec, P(),
                      P(), P()),
            out_specs=(q_spec, seq_spec, seq_spec, P()),
            check_vma=False)(q, kn, vn, ck, cv, cp, window, pos)
        ck = jax.lax.with_sharding_constraint(ck, seq_ns)
        cv = jax.lax.with_sharding_constraint(cv, seq_ns)
        return out, ck, cv, cp

    return hook


def make_activation_constraint(mesh: Mesh, *, batch_size: int):
    """Pin the batch axis of activations to ('pod','data')."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if not dp or batch_size % n_dp:
        return None

    def constrain(x):
        spec = P(*((dp,) + (None,) * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    return constrain


def batch_shardings(cfg: ModelConfig, batch_shapes: Dict[str, Any],
                    mesh: Mesh, *, batch_size: int) -> Dict[str, Any]:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    bspec = dp if (dp and batch_size % n_dp == 0) else None

    out = {}
    for k, v in batch_shapes.items():
        spec = [bspec] + [None] * (v.ndim - 1)
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def cache_shardings(cfg: ModelConfig, cache_shapes: Any, mesh: Mesh,
                    policy: Policy, *, batch_size: int,
                    long_context: bool) -> Any:
    """KV/state cache shardings.

    Leaf layouts (uniform archs have a leading L):
      k/v   (L, B, M, Hkv, hd)   pos (L, M)
      ssd.state (L, B, H, P, N)  conv tails (L, B, W-1, C)
      memory (B, M, d)
    """
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    batch_ax = "data" if batch_size % n_data == 0 else None
    # long-context, unshardable batch: shard the cache sequence over
    # EVERY axis (data x model) — the flash-decoding hook reduces over
    # both (DESIGN.md §5, EXPERIMENTS.md §Perf hillclimb 3)
    seq_ax = (("data", "model") if (long_context and policy.seq_shard_cache
                                    and batch_ax is None) else None)

    def f(path, leaf):
        p = _path_str(path)
        name = p.split("/")[-1]
        nd = leaf.ndim
        # stacked leading layer dim: uniform scan, or block-scan stacks
        lead = 1 if (("blocks" in p) or (cfg.uniform and "layers" in p)) \
            else 0
        spec: list = [None] * nd
        if name in ("k", "v") and nd == lead + 4:
            B, M, H, hd = leaf.shape[lead:]
            axes, bs = seq_shard_axes(mesh, batch_size, M, H)
            if bs:
                spec[lead] = "data"
                if axes:
                    # sequence over "model": the decode hook merges
                    # flash partials (W3); head_dim sharding would
                    # psum every score chunk instead
                    spec[lead + 1] = axes[0]
                elif H % n_model == 0:
                    # whole kv heads per shard: zero-collective (W6)
                    spec[lead + 2] = "model"
            elif axes and policy.seq_shard_cache:
                spec[lead + 1] = axes if len(axes) > 1 else axes[0]
        elif name in ("state", "h", "conv") and nd >= lead + 2:
            if batch_ax and leaf.shape[lead] % n_data == 0:
                spec[lead] = batch_ax
        elif name == "memory" and nd == 3:
            if batch_ax and leaf.shape[0] % n_data == 0:
                spec[0] = batch_ax
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(f, cache_shapes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ----------------------------------------------------------------------
# tensor-parallel paged serving (head-sharded page pools)
# ----------------------------------------------------------------------
#
# The continuous engine's TP mode (``serving.runner.ModelRunner`` with
# ``mesh=``) runs the whole paged forward inside ``shard_map`` over the
# ``model`` axis — the paper's node≅shard mapping, with each mesh shard
# standing in for one NUMA node.  The layout mirrors
# ``make_decode_attn_hook``: per-shard attention over purely local KV
# state, then ONE collective per layer to merge the partial outputs.
# Here the cache splits by **kv head** instead of by sequence, so the
# merge needs no LSE weighting — head outputs are disjoint, and the
# Gather is a zero-padded psum (``make_paged_head_merge``).  Everything
# outside attention (norms, MLP, embed/lm_head) stays replicated: the
# per-layer collective budget is exactly one all-reduce, and no
# collective ever touches KV-page bytes.

#: attention leaves sharded on their output-feature (head) dim in the
#: serving TP plan — the §3.2 "partitioned by attention heads" rule
SERVING_TP_HEAD_SHARDED = ("w_q", "w_k", "w_v", "b_q", "b_k", "b_v")


def serving_tp_param_specs(params_shapes: Any, *, axis: str = "model",
                           ) -> Any:
    """PartitionSpec tree for the paged TP serving engine.

    ``w_q/w_k/w_v`` (L, d, heads*hd) and their biases (L, heads*hd)
    shard their last (head) dim over ``axis``; every other leaf — w_o,
    MLP, norms, embed, lm_head — is replicated, so the only partial
    values in the forward are per-shard attention-head outputs and the
    one psum of :func:`make_paged_head_merge` restores full replication
    before ``w_o``.

    Q4_0 weights (``--quant q4``) replace a projection leaf with a
    ``{"q4_packed", "q4_scales"}`` subtree (``repro.quant.policy``);
    both members keep the original column (N) layout in their last dim,
    and Q4_0 quantizes along K — so sharding that last dim by the
    *parent* weight's rule yields byte-identical blocks to quantizing
    the already-sharded weight, and the same one-psum-per-layer budget
    holds.
    """
    def f(path, leaf):
        p = _path_str(path)
        parts = p.split("/")
        name = parts[-1]
        if name in ("q4_packed", "q4_scales") and len(parts) >= 2:
            name = parts[-2]
        if name in SERVING_TP_HEAD_SHARDED and "attn" in p:
            return P(*([None] * (leaf.ndim - 1) + [axis]))
        return P()
    return jax.tree_util.tree_map_with_path(f, params_shapes)


def paged_cache_specs(cache_shapes: Any, *, axis: str = "model") -> Any:
    """PartitionSpec tree for the paged device cache under TP.

    Each per-layer flat pool buffer (rows, Hkv * D) shards its **kv-head
    columns** over ``axis`` — contiguous blocks of whole heads, so every
    shard holds its head slice of every page, and page allocation,
    sharing, CoW and eviction stay pure host bookkeeping with zero
    cross-shard byte traffic.  Block tables (and anything else
    host-written) replicate.

    Int8 pools (``--kv-dtype int8``) add ``k_scale``/``v_scale``
    buffers (rows, Hkv) whose head dim shards exactly like the code
    buffers, so each shard dequantizes its local heads with local
    scales — still zero cross-shard KV traffic.
    """
    def f(path, leaf):
        name = _path_str(path).split("/")[-1]
        if name in ("k", "v", "k_scale", "v_scale") and leaf.ndim == 2:
            return P(None, axis)
        return P()
    return jax.tree_util.tree_map_with_path(f, cache_shapes)


def make_paged_head_merge(n_heads: int, n_shards: int, *,
                          axis: str = "model"):
    """Gather for head-sharded paged attention (§3.3 applied to heads).

    Inside the shard_map body each shard's attention output holds its
    ``n_heads / n_shards`` local query heads.  The merge scatters that
    slice into a zero tensor of the full head set at the shard's head
    offset and psums over ``axis`` — head supports are disjoint, so the
    sum is an exact concatenation (``x + 0.0 == x``), making the merged
    tensor **bit-identical** to the single-shard attention output.  One
    psum per layer, the TP forward's only collective.
    """
    import jax.numpy as jnp
    if n_heads % n_shards:
        raise ValueError(
            f"{n_heads} query heads do not shard over {n_shards} shards")
    local = n_heads // n_shards

    def merge(out):                       # out: (B, S, H_local, D)
        idx = jax.lax.axis_index(axis)
        full = jnp.zeros(out.shape[:2] + (n_heads,) + out.shape[3:],
                         out.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, out, idx * local, 2)
        return jax.lax.psum(full, axis)

    return merge
