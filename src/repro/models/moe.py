"""Mixture-of-Experts layer: top-k routing, expert parallelism, held shares.

The router scores all ``cfg.n_experts``; a layer holds the
``cfg.n_held_experts`` of them from ``cfg.first_expert`` on (all of them
by default; one chip's share under expert parallelism otherwise) and adds
what its own experts give. What the absent experts would add is left out.

Two forms:

* ``moe_apply_ep`` (training) — shard_map expert parallelism. Tokens are
  (data x model)-sharded; experts are model-sharded. Each rank routes its
  local tokens, packs fixed-capacity per-destination send buffers, and one
  ``all_to_all`` over the model axis moves tokens to the rank owning their
  expert (the return trip mirrors it). All scatters are local-shaped, so
  GSPMD never sees a partitioned scatter — the naive global scatter
  (``moe_apply``) makes XLA replicate [T, ...] buffers (observed: 191 GB
  temp/device on granite train_4k vs ~1 GB with this path). Experts take
  at most ``capacity_factor * t * k / e`` tokens each and drop the rest;
  ``moe_apply`` (single-device semantics) is its oracle.

* ``moe_apply_held`` (serving: prefill and decode) — dropless. A token
  picks an expert at most once, so every held expert takes a row for every
  token (a capacity of ``t`` rows, which no routing overflows) and each
  token adds the gated outputs of the held experts it routed to. On a mesh
  tokens stay replicated over the model axis, rank ``r`` holds ``e_loc``
  experts from ``first_expert + r * e_loc`` on, and a psum combines; on one
  chip the layer runs with the config's range and no exchange. It also
  returns the layer's routing counts: token-routings that landed on held
  experts, and held experts that took at least one token.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (dense_init, pdtype, rmsnorm, rmsnorm_init)


def moe_init(key, cfg: ModelConfig) -> Dict:
    d, ff, dt = cfg.d_model, cfg.d_ff, pdtype(cfg)
    e, eh = cfg.n_experts, cfg.n_held_experts
    ks = jax.random.split(key, 4)
    return {"router": dense_init(ks[0], d, e, jnp.float32, scale=0.02),
            "e_gate": (jax.random.normal(ks[1], (eh, d, ff)) * 0.02
                       ).astype(dt),
            "e_up": (jax.random.normal(ks[2], (eh, d, ff)) * 0.02).astype(dt),
            "e_down": (jax.random.normal(ks[3], (eh, ff, d)) * 0.02
                       ).astype(dt)}


def route(router: jnp.ndarray, xt: jnp.ndarray, k: int):
    """Softmax over every expert in float32, its top ``k``, renormalised:
    (gates [t, k], experts [t, k], probabilities [t, e])."""
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return gate_w, gate_i, probs


def moe_apply(params: Dict, cfg: ModelConfig,
              x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y, aux_loss). Capacity C = cf * T * k / E per
    held expert; routings to experts not held here add nothing."""
    b, s, d = x.shape
    t = b * s
    e, k, eh = cfg.n_experts, cfg.top_k, cfg.n_held_experts
    xt = x.reshape(t, d)
    gate_w, gate_i, probs = route(params["router"], xt, k)      # [T, k]

    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(axis=0)                                     # [E]
    ce = jnp.zeros((e,), jnp.float32).at[gate_i.reshape(-1)].add(
        1.0 / (t * k))
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    capacity = int(max(1, round(cfg.capacity_factor * t * k / e)))
    capacity = min(capacity, t)

    # --- positions in expert (slot-major priority: k=0 first) -----------
    flat_e = gate_i.T.reshape(-1) - cfg.first_expert           # [k*T]
    held = (flat_e >= 0) & (flat_e < eh)
    flat_e = jnp.where(held, flat_e, 0)
    onehot = jax.nn.one_hot(flat_e, eh, dtype=jnp.int32) \
        * held[:, None].astype(jnp.int32)                       # [k*T, Eh]
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # pre-count
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = held & (pos < capacity)
    slot_w = gate_w.T.reshape(-1) * keep                        # [k*T]

    # --- scatter dispatch: [Eh, C, d] ------------------------------------
    safe_pos = jnp.where(keep, pos, capacity - 1)
    src = jnp.tile(xt, (k, 1))
    buf = jnp.zeros((eh, capacity, d), xt.dtype)
    buf = buf.at[flat_e, safe_pos].add(jnp.where(keep[:, None], src, 0))

    # --- expert compute (E over the "model" axis) ------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["e_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["e_up"])
    y_e = jnp.einsum("ecf,efd->ecd", h, params["e_down"])       # [Eh, C, d]

    # --- gather combine (gate applied at combine: y = sum_i g_i e_i(x)) ---
    vals = y_e[flat_e, safe_pos]                                # [k*T, d]
    vals = vals * slot_w[:, None].astype(vals.dtype)
    y = vals.reshape(k, t, d).sum(axis=0)
    return y.reshape(b, s, d), aux


def _mesh_axis_size(name: str) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    return dict(mesh.shape).get(name, 1)


def _capacity(cf: float, tokens: int, k: int, buckets: int) -> int:
    return int(max(1, round(cf * tokens * k / buckets)))


def moe_apply_ep(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                 dp_axes="data") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map + all_to_all (train/prefill).

    x: [B, S, d], tokens sharded (dp_axes x model); experts sharded over
    "model". Per rank: route local tokens -> pack per-destination send
    buffers (capacity-bounded) -> all_to_all over model -> local expert
    compute -> all_to_all back -> gated combine. Equivalent to
    ``moe_apply`` on a 1x1 mesh (same capacity discipline & priority).
    """
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    nm = _mesh_axis_size("model")
    b, s, _ = x.shape
    if nm == 1 or e % nm or s % nm or cfg.n_held_experts != e:
        return moe_apply(params, cfg, x)
    e_loc = e // nm
    cf = cfg.capacity_factor

    x = jax.lax.with_sharding_constraint(x, P(dp_axes, "model", None))
    x_spec = P(dp_axes, "model", None)
    ep_spec = P("model", None, None)

    def local(xl, wr, wg, wu, wd):
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)

        # ---- routing --------------------------------------------------
        gate_w, gate_i, probs = route(wr, xt, k)                # [t, k]

        # load-balance aux (global via pmean over every token shard)
        all_axes = ((dp_axes,) if isinstance(dp_axes, str)
                    else tuple(dp_axes)) + ("model",)
        me = jax.lax.pmean(probs.mean(axis=0), all_axes)        # [E]
        ce = jnp.zeros((e,), jnp.float32).at[gate_i.reshape(-1)].add(
            1.0 / (t * k))
        ce = jax.lax.pmean(ce, all_axes)
        aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

        # ---- stage 1: pack per-destination send buffers ----------------
        flat_e = gate_i.T.reshape(-1)                           # [k*t]
        flat_g = gate_w.T.reshape(-1)
        tok_of_slot = jnp.tile(jnp.arange(t, dtype=jnp.int32), (k,))
        dest = flat_e // e_loc                                  # [k*t]
        cd = _capacity(cf, t, k, nm)
        onehot_d = jax.nn.one_hot(dest, nm, dtype=jnp.int32)
        posd = jnp.cumsum(onehot_d, axis=0) - onehot_d
        posd = jnp.take_along_axis(posd, dest[:, None], axis=1)[:, 0]
        keep1 = posd < cd
        safe1 = jnp.where(keep1, posd, cd - 1)

        send_x = jnp.zeros((nm, cd, d), xt.dtype).at[dest, safe1].add(
            jnp.where(keep1[:, None], jnp.take(xt, tok_of_slot, axis=0), 0))
        e_local_id = (flat_e % e_loc).astype(jnp.int32)
        send_meta = jnp.zeros((nm, cd), jnp.int32).at[dest, safe1].max(
            jnp.where(keep1, e_local_id + 1, 0))
        # local bookkeeping for the return trip (never leaves the rank)
        ret_tok = jnp.full((nm, cd), t, jnp.int32).at[dest, safe1].min(
            jnp.where(keep1, tok_of_slot, t))
        ret_gate = jnp.zeros((nm, cd), jnp.float32).at[dest, safe1].add(
            jnp.where(keep1, flat_g, 0.0))

        # ---- all_to_all dispatch over the model axis -------------------
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=True)
        recv_meta = jax.lax.all_to_all(send_meta, "model", 0, 0, tiled=True)
        recv_e = recv_meta.reshape(-1) - 1                      # [nm*cd]
        recv_ok = recv_e >= 0
        recv_e = jnp.where(recv_ok, recv_e, 0)
        rx = recv_x.reshape(nm * cd, d)

        # ---- stage 2: local per-expert dispatch ------------------------
        ce_cap = _capacity(cf, t * nm, k, e)
        onehot_e = jax.nn.one_hot(recv_e, e_loc, dtype=jnp.int32)
        onehot_e = onehot_e * recv_ok[:, None].astype(jnp.int32)
        pose = jnp.cumsum(onehot_e, axis=0) - onehot_e
        pose = jnp.take_along_axis(pose, recv_e[:, None], axis=1)[:, 0]
        keep2 = recv_ok & (pose < ce_cap)
        safe2 = jnp.where(keep2, pose, ce_cap - 1)
        buf = jnp.zeros((e_loc, ce_cap, d), xt.dtype).at[recv_e, safe2].add(
            jnp.where(keep2[:, None], rx, 0))

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        y_e = jnp.einsum("ecf,efd->ecd", h, wd)                 # [e_loc,C,d]

        # ---- return trip ------------------------------------------------
        back = y_e[recv_e, safe2]
        back = jnp.where(keep2[:, None], back, 0).reshape(nm, cd, d)
        ret = jax.lax.all_to_all(back, "model", 0, 0, tiled=True)

        # ---- gated combine ----------------------------------------------
        ret = ret.reshape(nm * cd, d) \
            * ret_gate.reshape(-1)[:, None].astype(y_e.dtype)
        y_t = jnp.zeros((t + 1, d), y_e.dtype).at[
            ret_tok.reshape(-1)].add(ret)[:t]
        return y_t.reshape(bl, sl, d).astype(xl.dtype), aux

    return jax.shard_map(
        local,
        in_specs=(x_spec, P(None, None), ep_spec, ep_spec, ep_spec),
        out_specs=(x_spec, P()))(
            x, params["router"], params["e_gate"], params["e_up"],
            params["e_down"])


def _held_part(xt, wr, wg, wu, wd, first, k: int):
    """What experts [first, first + e) give each token of ``xt`` [t, d],
    dropless, with (routings landing here, experts touched) as int32."""
    gate_w, gate_i, _ = route(wr, xt, k)
    onehot = jax.nn.one_hot(gate_i - first, wg.shape[0],
                            dtype=jnp.float32)                  # [t, k, e]
    gates = (gate_w[..., None] * onehot).sum(axis=1).T          # [e, t]
    routed = onehot.sum(axis=1).T > 0
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, wg)) \
            * jnp.einsum("td,edf->etf", xt, wu)
        y_e = jnp.einsum("etf,efd->etd", h, wd)                 # [e, t, d]
        y = (gates[..., None] * y_e.astype(jnp.float32)).sum(axis=0)
    counts = jnp.stack([routed.sum(), routed.any(axis=1).sum()])
    return y.astype(xt.dtype), counts.astype(jnp.int32)


def moe_apply_held(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                   dp_axes="data") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless MoE over the held experts (prefill and decode).

    x: [B, S, d] -> (y, counts int32[2]: token-routings that landed on
    held experts, held experts with at least one token). On a mesh each
    data shard's batch counts its own touches, as each chip's would.
    """
    eh, k, d = cfg.n_held_experts, cfg.top_k, cfg.d_model
    nm = _mesh_axis_size("model")
    weights = (params["router"], params["e_gate"], params["e_up"],
               params["e_down"])
    if nm == 1 or eh % nm:
        y, counts = _held_part(x.reshape(-1, d), *weights, cfg.first_expert,
                               k)
        return y.reshape(x.shape), counts
    e_loc = eh // nm
    axes = ((dp_axes,) if isinstance(dp_axes, str) else tuple(dp_axes)) \
        + ("model",)
    x_spec = P(dp_axes, None, None)
    ep_spec = P("model", None, None)

    def local(xl, wr, wg, wu, wd):
        first = cfg.first_expert + jax.lax.axis_index("model") * e_loc
        y, counts = _held_part(xl.reshape(-1, d), wr, wg, wu, wd, first, k)
        return (jax.lax.psum(y, "model").reshape(xl.shape),
                jax.lax.psum(counts, axes))

    return jax.shard_map(
        local,
        in_specs=(x_spec, P(None, None), ep_spec, ep_spec, ep_spec),
        out_specs=(x_spec, P()))(x, *weights)


def moe_block_init(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 2)
    return {"ln_attn": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "attn": attn.attn_init(ks[0], cfg),
            "ln_mlp": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "moe": moe_init(ks[1], cfg)}


def moe_block_apply(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    positions: jnp.ndarray, *, causal: bool = True,
                    fuse_qkv: bool = True, q_block: int = 512,
                    kv_block: int = 512, dp_axes="data"):
    h = rmsnorm(params["ln_attn"], x, cfg.norm_eps)
    q, kk, v = attn.qkv_project(params["attn"], cfg, h, positions,
                                fuse_qkv=fuse_qkv)
    o = attn.chunked_attention(q, kk, v, causal=causal, q_block=q_block,
                               kv_block=kv_block)
    b, s, _, _ = o.shape
    x = x + o.reshape(b, s, cfg.q_dim) @ params["attn"]["wo"]
    h = rmsnorm(params["ln_mlp"], x, cfg.norm_eps)
    y, aux = moe_apply_ep(params["moe"], cfg, h, dp_axes=dp_axes)
    return x + y, aux


def moe_block_decode_paged(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                           pos: jnp.ndarray, kv: Dict, *, batch_axes,
                           page_axes, fuse_qkv: bool = True,
                           kv_block: int = 2048):
    """Single-token decode against a page-sharded cache (see
    transformer.block_decode_paged); also returns the MoE layer's routing
    counts."""
    h = rmsnorm(params["ln_attn"], x, cfg.norm_eps)
    positions = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1, 1), (x.shape[0], 1))
    q, kk, v = attn.qkv_project(params["attn"], cfg, h, positions,
                                fuse_qkv=fuse_qkv)
    if "k_scale" in kv:
        o, k_pages, v_pages, k_scale, v_scale = attn.paged_decode_attention(
            q, kv["k"], kv["v"], kk, v, pos, batch_axes=batch_axes,
            page_axes=page_axes, kv_block=kv_block,
            k_scale=kv["k_scale"], v_scale=kv["v_scale"])
        kv_out = {"k": k_pages, "v": v_pages, "k_scale": k_scale,
                  "v_scale": v_scale}
    else:
        o, k_pages, v_pages = attn.paged_decode_attention(
            q, kv["k"], kv["v"], kk, v, pos, batch_axes=batch_axes,
            page_axes=page_axes, kv_block=kv_block)
        kv_out = {"k": k_pages, "v": v_pages}
    x = x + o.reshape(x.shape[0], 1, cfg.q_dim) @ params["attn"]["wo"]
    h = rmsnorm(params["ln_mlp"], x, cfg.norm_eps)
    y, counts = moe_apply_held(params["moe"], cfg, h,
                               dp_axes=batch_axes or "data")
    return x + y, kv_out, counts

