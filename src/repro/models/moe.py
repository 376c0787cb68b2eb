"""Mixture-of-Experts FFN with expert parallelism (GShard-style, shard_map).

Two execution paths share one routing/dispatch core:

* **train/prefill** (`moe_shard_map`) — tokens arrive sequence-sharded over
  the ``model`` axis (SP residual stream) and batch-sharded over
  ``(pod, data)``; experts are sharded over ``model``.  Each shard routes
  its local tokens, builds a capacity-bounded (E, C, d) dispatch, and two
  ``all_to_all`` collectives move tokens to expert owners and back — the
  canonical EP schedule, with exact active-FLOPs batched GEMMs
  (``ecd,edf->ecf``).
* **decode** (`moe_einsum`) — token counts are tiny (≤ global batch), so a
  dense one-hot dispatch einsum under plain pjit is cheaper than paying the
  shard_map/a2a latency; XLA propagates the expert sharding.

Capacity overflow drops tokens (zero contribution), as in GShard; tests
validate exactness against the dense reference at high capacity factors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import ParamDef

Array = jax.Array


def moe_defs(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    defs = {
        "router": ParamDef((d, e), ("fsdp", "none"), scale=d**-0.5),
        # d_ff (not d_model) carries the data-axis storage split: see
        # ShardingRules.expert_ff — decode reads experts gather-free.
        "gate": ParamDef((e, d, f), ("experts", "none", "expert_ff"), scale=d**-0.5),
        "up": ParamDef((e, d, f), ("experts", "none", "expert_ff"), scale=d**-0.5),
        "down": ParamDef((e, f, d), ("experts", "expert_ff", "none"), scale=f**-0.5),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        defs["shared_gate"] = ParamDef((d, fs), ("fsdp", "ff"), scale=d**-0.5)
        defs["shared_up"] = ParamDef((d, fs), ("fsdp", "ff"), scale=d**-0.5)
        defs["shared_down"] = ParamDef((fs, d), ("ff", "fsdp"), scale=fs**-0.5)
    return defs


def _route(x2d: Array, wr: Array, k: int, softmax_topk: bool):
    """-> (ids (T,k) int32, gates (T,k) f32, probs (T,E) f32)."""
    logits = (x2d.astype(jnp.float32)) @ wr.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_ids = lax.top_k(logits, k)
    if softmax_topk:
        gates = jax.nn.softmax(top_vals, axis=-1)
    else:
        gates = jax.nn.sigmoid(top_vals)
    return top_ids.astype(jnp.int32), gates, probs


def _capacity(tokens: int, k: int, e: int, factor: float) -> int:
    c = int(tokens * k / e * factor) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def _dispatch_sorted(ids: Array, gates: Array, tokens: int, e: int, cap: int):
    """Sort-based capacity dispatch.

    -> buf_tok (E, C) int32 token index or -1; buf_gate (E, C) f32.
    """
    t, k = ids.shape
    flat_e = ids.reshape(t * k)
    flat_g = gates.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)  # slot order grouped by expert
    sorted_e = flat_e[order]
    # Rank within the expert group = position - group start.
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos = jnp.arange(t * k) - group_start[sorted_e]
    keep = pos < cap
    e_idx = jnp.where(keep, sorted_e, 0)
    p_idx = jnp.where(keep, pos, cap - 1)
    tok = jnp.where(keep, order // k, -1)
    gat = jnp.where(keep, flat_g[order], 0.0)
    buf_tok = jnp.full((e, cap), -1, jnp.int32).at[e_idx, p_idx].max(
        tok.astype(jnp.int32), mode="drop"
    )
    buf_gate = jnp.zeros((e, cap), jnp.float32).at[e_idx, p_idx].max(
        gat, mode="drop"
    )
    buf_gate = jnp.where(buf_tok >= 0, buf_gate, 0.0)
    return buf_tok, buf_gate


def _expert_ffn(xe: Array, p: dict, dtype) -> Array:
    """(E_loc, C', d) tokens through per-expert SwiGLU."""
    wg = p["gate"].astype(dtype)
    wu = p["up"].astype(dtype)
    wd = p["down"].astype(dtype)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    h = g * jnp.einsum("ecd,edf->ecf", xe, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _aux_loss(probs: Array, ids: Array, e: int) -> Array:
    """Switch/GShard load-balance loss: E * sum_e f_e * p_e."""
    onehot = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)
    f = onehot.mean(axis=0)
    pbar = probs.mean(axis=0)
    return e * jnp.sum(f * pbar)


def _shared_ffn(p: dict, x: Array) -> Array:
    g = jax.nn.silu(x @ p["shared_gate"].astype(x.dtype))
    h = g * (x @ p["shared_up"].astype(x.dtype))
    return h @ p["shared_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# shard_map path (train / prefill)
# ---------------------------------------------------------------------------

def _moe_body(x, p, *, cfg, model_axis: str | None, ep: int,
              batch_axes: tuple = (), ff_axis: str | None = None):
    """x (B_loc, S_loc, d) local tokens; expert weights local (E_loc,...).

    ``ff_axis``: weight-stationary second EP level — expert matrices keep
    their d_ff shards on the ``ff_axis`` (= the storage split, see
    ShardingRules.expert_ff) and TOKENS move instead: all-gather the
    dispatched tokens over ``ff_axis``, compute the f-sliced partial FFN,
    psum-scatter the partial outputs back.  Token payloads are
    microbatch-proportional; weight payloads are not — measured 3-4x fewer
    collective bytes on jamba/dbrx train cells (EXPERIMENTS.md §Perf).
    """
    bl, sl, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    tloc = bl * sl
    cap = _capacity(tloc, k, e, cfg.capacity_factor)
    x2d = x.reshape(tloc, d)

    ids, gates, probs = _route(x2d, p["router"], k, cfg.router_softmax_topk)
    buf_tok, buf_gate = _dispatch_sorted(ids, gates, tloc, e, cap)
    xe = jnp.where(
        (buf_tok >= 0)[..., None], x2d[jnp.clip(buf_tok, 0)], 0
    )  # (E, C, d)

    if ep > 1:
        # tokens -> expert owners: (E, C, d) -> (E/ep, ep*C, d)
        xe = lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=1,
                            tiled=True)
    if ff_axis is not None:
        # level 2: bring every ff-shard this expert's tokens (tokens are
        # small; the weights stay put)
        xe = lax.all_gather(xe, ff_axis, axis=1, tiled=True)
    ye = _expert_ffn(xe, p, x.dtype)
    if ff_axis is not None:
        # sum the f-sliced partials and return each shard its own tokens
        ye = lax.psum_scatter(ye, ff_axis, scatter_dimension=1, tiled=True)
    if ep > 1:
        ye = lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0,
                            tiled=True)

    contrib = ye * buf_gate[..., None].astype(ye.dtype)  # (E, C, d)
    y2d = jnp.zeros((tloc, d), x.dtype).at[jnp.clip(buf_tok, 0)].add(
        jnp.where((buf_tok >= 0)[..., None], contrib, 0), mode="drop"
    )
    y = y2d.reshape(bl, sl, d)
    if cfg.num_shared_experts:
        y = y + _shared_ffn(p, x)
    # Invariant scalar aux loss: mean over every shard's local loss.
    aux = _aux_loss(probs, ids, e)
    reduce_axes = tuple(batch_axes) + ((model_axis,) if ep > 1 else ())
    if reduce_axes:
        aux = lax.pmean(aux, reduce_axes)
    return y, aux


def moe_apply(
    p: dict,
    x: Array,
    *,
    cfg,
    mesh: Mesh | None,
    batch_axes: tuple = ("pod", "data"),
    model_axis: str = "model",
) -> tuple[Array, Array]:
    """MoE FFN. x (B, S, d) -> (y, aux_loss).

    Uses the shard_map EP path when a mesh with a model axis is present and
    the sequence is shardable; otherwise the einsum path (decode / smoke).
    """
    e = cfg.num_experts
    if mesh is not None:
        batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
        have_model = model_axis in mesh.shape
    else:
        have_model = False

    seq_ok = have_model and x.shape[1] % mesh.shape[model_axis] == 0
    if mesh is None or not have_model or not seq_ok or x.shape[1] == 1:
        return moe_einsum(p, x, cfg=cfg)

    ep = mesh.shape[model_axis]
    # Weight-stationary second level: keep the d_ff storage shards in place
    # when they exist (mirror of ShardingRules.expert_ff + divisibility).
    ff_axis = (
        "data"
        if cfg.fsdp and "data" in mesh.shape
        and cfg.d_ff % mesh.shape["data"] == 0
        else None
    )
    body = functools.partial(
        _moe_body, cfg=cfg, model_axis=model_axis, ep=ep,
        batch_axes=batch_axes, ff_axis=ff_axis,
    )
    wff = ff_axis  # None -> gathered by shard_map (legacy ZeRO-style path)
    in_specs = (
        P(batch_axes, model_axis, None),  # x: batch + sequence sharded
        {
            "router": P(),
            "gate": P(model_axis, None, wff),
            "up": P(model_axis, None, wff),
            "down": P(model_axis, wff, None),
            **(
                {
                    "shared_gate": P(),
                    "shared_up": P(),
                    "shared_down": P(),
                }
                if cfg.num_shared_experts
                else {}
            ),
        },
    )
    out_specs = (P(batch_axes, model_axis, None), P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    pl = {key: p[key] for key in in_specs[1]}
    y, aux = fn(x, pl)
    return y, aux


# ---------------------------------------------------------------------------
# einsum path (decode / single device)
# ---------------------------------------------------------------------------

def moe_einsum(p: dict, x: Array, *, cfg) -> tuple[Array, Array]:
    """One-hot dispatch einsum MoE (small token counts)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    cap = _capacity(t, k, e, cfg.capacity_factor)
    x2d = x.reshape(t, d)
    ids, gates, probs = _route(x2d, p["router"], k, cfg.router_softmax_topk)
    buf_tok, buf_gate = _dispatch_sorted(ids, gates, t, e, cap)
    xe = jnp.where((buf_tok >= 0)[..., None], x2d[jnp.clip(buf_tok, 0)], 0)
    ye = _expert_ffn(xe, p, x.dtype)
    contrib = ye * buf_gate[..., None].astype(ye.dtype)
    y2d = jnp.zeros((t, d), x.dtype).at[jnp.clip(buf_tok, 0)].add(
        jnp.where((buf_tok >= 0)[..., None], contrib, 0), mode="drop"
    )
    y = y2d.reshape(b, s, d)
    if cfg.num_shared_experts:
        y = y + _shared_ffn(p, x)
    return y, _aux_loss(probs, ids, e)


def moe_dense_reference(p: dict, x: Array, *, cfg) -> Array:
    """Oracle: full dense compute over every expert (tests only)."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    ids, gates, _ = _route(x2d, p["router"], cfg.experts_per_token,
                           cfg.router_softmax_topk)
    y = jnp.zeros_like(x2d)
    for e_idx in range(cfg.num_experts):
        g = jax.nn.silu(x2d @ p["gate"][e_idx].astype(x.dtype))
        h = g * (x2d @ p["up"][e_idx].astype(x.dtype))
        ye = h @ p["down"][e_idx].astype(x.dtype)
        w = ((ids == e_idx).astype(jnp.float32) * gates).sum(axis=1)
        y = y + ye * w[:, None].astype(x.dtype)
    y = y.reshape(b, s, d)
    if cfg.num_shared_experts:
        y = y + _shared_ffn(p, x.reshape(b, s, d))
    return y
