"""Mixture-of-experts block: top-k routing, capacity dispatch, EP/TP sharding.

Two dispatch strategies, selectable at build time:

- ``dispatch="scatter"`` (default): sort-free capacity dispatch via scatter-add
  into an (E, C, D) buffer. Pure jnp, runs on one device and under GSPMD.
- ``dispatch="a2a"``: shard_map expert parallelism with explicit
  ``lax.all_to_all`` over the model axis (hillclimb path; see EXPERIMENTS.md §Perf).

Routing is standard Switch/Mixtral: softmax router, top-k experts per token,
probability re-normalization over the chosen k, capacity drop, load-balancing
auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, silu


def init_moe(key, cfg, dtype) -> dict:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "wi": dense_init(ks[1], (e, d, f), d, dtype),
        "wo": dense_init(ks[2], (e, f, d), f, dtype),
    }
    if cfg.act == "swiglu":
        p["wg"] = dense_init(ks[3], (e, d, f), d, dtype)
    return p


def route(cfg, p, x_flat):
    """x_flat (T,D) -> (weights (T,k), ids (T,k), aux_loss scalar)."""
    moe = cfg.moe
    logits = (x_flat.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, moe.top_k)                 # (T,k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * p_e
    e = moe.num_experts
    me = probs.mean(0)                                             # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0) / ids.size
    aux = e * jnp.sum(me * ce) * moe.router_aux_weight
    return weights, ids, aux


def _capacity(cfg, tokens: int) -> int:
    moe = cfg.moe
    c = int(tokens * moe.top_k * moe.capacity_factor / moe.num_experts)
    return max(8, -(-c // 8) * 8)


def _positions_in_expert(flat_ids, num_experts):
    """Rank of each routed (token,slot) within its expert, computed via sort."""
    n = flat_ids.shape[0]
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts                          # (E,)
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_ids]
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def _expert_ffn(cfg, p, buf):
    """buf (E, C, D) -> (E, C, D) through per-expert FFN."""
    cdt = buf.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cdt))
    if cfg.act == "swiglu":
        h = silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cdt))) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cdt))


def moe_block_scatter(cfg, p, x, sharder=None):
    """x (B,S,D) -> (out (B,S,D), aux_loss).

    Batch-row-grouped capacity dispatch: every batch row routes its own S
    tokens into a PRIVATE (E, C_row, D) buffer, so the stacked buffer
    (B, E, C_row, D) carries the data-parallel batch dim and shards over
    ("pod","data") like every other activation. The pre-grouping variant
    (kept below as ``moe_block_scatter_global``) builds one global (E, C, D)
    buffer whose token axis CANNOT shard -> every device all-reduces and
    computes the full global capacity buffer (the 522 s/step baseline of
    EXPERIMENTS.md §Perf / grok-1).
    """
    moe = cfg.moe
    B, S, D = x.shape
    k = moe.top_k
    xf = x.reshape(B * S, D)
    weights, ids, aux = route(cfg, p, xf)                          # (B*S, k)
    C = _capacity(cfg, S)                                          # per row
    ids_r = ids.reshape(B, S * k)
    pos = jax.vmap(lambda fi: _positions_in_expert(fi, moe.num_experts))(ids_r)
    keep = pos < C                                                 # (B, S*k)
    pos_c = jnp.where(keep, pos, 0)
    x_rep = jnp.repeat(x, k, axis=1)                               # (B, S*k, D)

    def row_dispatch(xb, ib, pb, kb):
        buf = jnp.zeros((moe.num_experts, C, D), x.dtype)
        return buf.at[ib, pb].add(xb * kb[:, None].astype(x.dtype))

    buf = jax.vmap(row_dispatch)(x_rep, ids_r, pos_c, keep)        # (B,E,C,D)
    if sharder is not None:
        which = "expert" if moe.expert_sharding == "ep" else None
        buf = sharder.constrain(buf, "batch", which, None, None)

    out_buf = _expert_ffn_batched(cfg, p, buf)                     # (B,E,C,D)
    if sharder is not None:
        which = "expert" if moe.expert_sharding == "ep" else None
        out_buf = sharder.constrain(out_buf, "batch", which, None, None)

    gathered = jax.vmap(lambda ob, ib, pb: ob[ib, pb])(out_buf, ids_r, pos_c)
    wk = (weights.reshape(B, S * k) * keep).astype(x.dtype)
    y = (gathered * wk[..., None]).reshape(B, S, k, D).sum(axis=2)
    return y, aux


def _expert_ffn_batched(cfg, p, buf):
    """buf (B, E, C, D) -> (B, E, C, D) through per-expert FFNs."""
    cdt = buf.dtype
    h = jnp.einsum("becd,edf->becf", buf, p["wi"].astype(cdt))
    if cfg.act == "swiglu":
        h = silu(jnp.einsum("becd,edf->becf", buf, p["wg"].astype(cdt))) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("becf,efd->becd", h, p["wo"].astype(cdt))


def moe_block_scatter_global(cfg, p, x, sharder=None):
    """The pre-optimization dispatch (one global (E,C,D) buffer). Kept as the
    paper-faithful-baseline / ablation arm for EXPERIMENTS.md §Perf."""
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    k = moe.top_k
    xf = x.reshape(T, D)
    weights, ids, aux = route(cfg, p, xf)
    C = _capacity(cfg, T)
    flat_ids = ids.reshape(-1)                                     # (T*k,)
    pos = _positions_in_expert(flat_ids, moe.num_experts)          # (T*k,)
    keep = (pos < C)
    pos_c = jnp.where(keep, pos, 0)

    # dispatch: (E, C, D) — token slot j of expert e
    x_rep = jnp.repeat(xf, k, axis=0)                              # (T*k, D)
    buf = jnp.zeros((moe.num_experts, C, D), x.dtype)
    buf = buf.at[flat_ids, pos_c].add(x_rep * keep[:, None].astype(x.dtype))
    if sharder is not None:
        which = "expert" if moe.expert_sharding == "ep" else None
        buf = sharder.constrain(buf, which, None, None)

    out_buf = _expert_ffn(cfg, p, buf)                             # (E, C, D)

    # combine
    gathered = out_buf[flat_ids, pos_c]                            # (T*k, D)
    wk = (weights.reshape(-1) * keep).astype(x.dtype)
    y = (gathered * wk[:, None]).reshape(T, k, D).sum(axis=1)
    return y.reshape(B, S, D), aux


def moe_block_a2a(cfg, p, x, sharder):
    """Expert-parallel MoE with explicit all_to_all over the model axis.

    Requires a mesh with a "model" axis and E % model_size == 0. Tokens are
    processed per model-shard (the batch is replicated over "model" outside,
    so each model shard handles a 1/model_size slice of the token stream).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    mesh = sharder.mesh
    m = mesh.shape["model"]
    assert moe.num_experts % m == 0, "a2a dispatch needs E % model == 0"
    B, S, D = x.shape
    batch_axes = sharder.axis_map.get("batch", ())

    def local_moe(xl, router, wi, wg, wo):
        # xl: (Bl, S_l, D) local tokens; experts local slice wi (E/m, D, F)
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xf = xl.reshape(Tl, D)
        pl = {"router": router, "wi": wi, "wo": wo}
        if wg is not None:
            pl["wg"] = wg
        weights, ids, aux = route(cfg, {"router": router}, xf)
        C = _capacity(cfg, Tl)
        C = max(8, -(-C // m) * m)  # divisible by model size for all_to_all
        flat_ids = ids.reshape(-1)
        pos = _positions_in_expert(flat_ids, moe.num_experts)
        keep = pos < C
        pos_c = jnp.where(keep, pos, 0)
        x_rep = jnp.repeat(xf, moe.top_k, axis=0)
        buf = jnp.zeros((moe.num_experts, C, D), xl.dtype)
        buf = buf.at[flat_ids, pos_c].add(x_rep * keep[:, None].astype(xl.dtype))
        # exchange: every shard sends its tokens for experts e to the shard
        # owning e; receive C tokens per peer -> (E/m, m*C, D)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xl.dtype))
        if wg is not None:
            h = silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype))) * h
        else:
            h = jax.nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(xl.dtype))
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0, tiled=True)
        gathered = out[flat_ids, pos_c]
        wk = (weights.reshape(-1) * keep).astype(xl.dtype)
        y = (gathered * wk[:, None]).reshape(Tl, moe.top_k, D).sum(axis=1)
        return y.reshape(Bl, Sl, D), aux

    bspec = P(batch_axes if batch_axes else None, "model", None)
    wg = p.get("wg")
    y, aux = shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None) if wg is not None else P(None),
                  P("model", None, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], wg if wg is not None else jnp.zeros((1,), x.dtype), p["wo"])
    return y, aux


def moe_block_tp(cfg, p, x, sharder):
    """TP-inside-expert MoE (few huge experts, e.g. grok-1) with DEFERRED
    combine: each model shard runs the full dispatch on its F-slice of every
    expert, combines its partial token outputs locally, and ONE psum of the
    (B_local, S, D) token stream replaces the all-reduce of the 2.5x-larger
    (E, C, D) capacity buffer (EXPERIMENTS.md §Perf, grok iteration 2).

    Gradient-exact vs moe_block_scatter (tests/test_moe_dispatch.py)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    mesh = sharder.mesh
    B, S, D = x.shape
    k = moe.top_k
    batch_axes = sharder.axis_map.get("batch", ())
    has_wg = "wg" in p

    def local(xl, router, wi, wg, wo):
        Bl, Sl, _ = xl.shape
        xf = xl.reshape(Bl * Sl, D)
        weights, ids, aux = route(cfg, {"router": router}, xf)
        C = _capacity(cfg, Sl)
        ids_r = ids.reshape(Bl, Sl * k)
        pos = jax.vmap(lambda fi: _positions_in_expert(fi, moe.num_experts))(ids_r)
        keep = pos < C
        pos_c = jnp.where(keep, pos, 0)
        x_rep = jnp.repeat(xl, k, axis=1)

        def row(xb, ib, pb, kb):
            return jnp.zeros((moe.num_experts, C, D), xl.dtype) \
                .at[ib, pb].add(xb * kb[:, None].astype(xl.dtype))

        buf = jax.vmap(row)(x_rep, ids_r, pos_c, keep)             # (Bl,E,C,D)
        cdt = xl.dtype
        h = jnp.einsum("becd,edf->becf", buf, wi.astype(cdt))
        if has_wg:
            h = silu(jnp.einsum("becd,edf->becf", buf, wg.astype(cdt))) * h
        else:
            h = jax.nn.gelu(h)
        out = jnp.einsum("becf,efd->becd", h, wo.astype(cdt))      # partial/model
        gathered = jax.vmap(lambda ob, ib, pb: ob[ib, pb])(out, ids_r, pos_c)
        wk = (weights.reshape(Bl, Sl * k) * keep).astype(cdt)
        y = (gathered * wk[..., None]).reshape(Bl, Sl, k, D).sum(axis=2)
        y = jax.lax.psum(y, "model")                               # combine-then-AR
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    bspec = P(batch_axes if batch_axes else None, None, None)
    wg_arg = p["wg"] if has_wg else jnp.zeros((1, 1, 1), x.dtype)
    wg_spec = P(None, None, "model") if has_wg else P(None, None, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(None, None), P(None, None, "model"), wg_spec,
                  P(None, "model", None)),
        out_specs=(bspec, P()), check_vma=False,
    )(x, p["router"], p["wi"], wg_arg, p["wo"])


def moe_block(cfg, p, x, sharder=None, dispatch: str = "scatter"):
    """Dispatch selection. On a mesh, "scatter" auto-routes to the measured-
    best variant per expert sharding (EXPERIMENTS.md §Perf A1/A2/A4):
      - EP experts  -> explicit all_to_all shard_map (arctic: 1.9x vs GSPMD)
      - TP experts  -> deferred-combine shard_map (grok: 1.5x vs GSPMD)
    "scatter_gspmd" forces the grouped GSPMD path; "scatter_global" is the
    pre-optimization baseline kept for §Perf ablations."""
    moe_cfg = cfg.moe
    has_model_axis = (sharder is not None and sharder.mesh is not None
                      and "model" in sharder.mesh.shape)
    ep_divisible = has_model_axis and moe_cfg.expert_sharding == "ep" \
        and moe_cfg.num_experts % sharder.mesh.shape["model"] == 0 \
        and x.shape[1] % sharder.mesh.shape["model"] == 0  # a2a slices tokens
    if dispatch == "scatter_global":
        return moe_block_scatter_global(cfg, p, x, sharder)
    if dispatch == "scatter_gspmd":
        return moe_block_scatter(cfg, p, x, sharder)
    if dispatch in ("a2a", "scatter") and ep_divisible:
        return moe_block_a2a(cfg, p, x, sharder)
    if has_model_axis and moe_cfg.expert_sharding == "tp":
        return moe_block_tp(cfg, p, x, sharder)
    return moe_block_scatter(cfg, p, x, sharder)
