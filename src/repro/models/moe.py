"""Mixture-of-Experts FFN with two dispatch strategies.

``gather`` (default): activations stay replicated across the ``model`` axis (as TP
leaves them); every model-axis member gathers the tokens routed to ITS local experts
(a purely local sort+scatter into a capacity-padded (E_local, C, D) buffer), runs its
experts, scatter-adds weighted outputs and psums over ``model``.  One all-reduce per
MoE layer — same wire cost as a TP MLP — and **no all-to-all**.

``a2a`` (paper-faithful expert parallelism): tokens are sharded over BOTH mesh axes;
each shard routes its tokens, packs per-destination capacity-padded send buffers,
exchanges them with ``lax.all_to_all`` over ``model`` (the DLRM alltoallv analogue —
the collective the BLS pipeline decouples), computes local experts, and all_to_alls
results back.  Raggedness -> padding, measured by ``dispatch_stats``.

Both modes share the same local sort-based dispatch and are allclose-tested against
``moe_ref_dense`` (every token through its experts, no capacity drop).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import layers as L
from repro.sharding import partition

# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def padded_experts(moe: MoEConfig, n_shards: int) -> int:
    e = moe.n_experts
    return ((e + n_shards - 1) // n_shards) * n_shards


def init_moe(key, cfg: ModelConfig, n_shards: int = 16):
    moe = cfg.moe
    d, f = cfg.d_model, moe.d_expert
    e_pad = padded_experts(moe, n_shards)
    kr, kg, ku, kd, ks, ksg = jax.random.split(key, 6)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": L.truncated_normal(kr, (d, e_pad), s_in, jnp.float32),
        "gate": L.truncated_normal(kg, (e_pad, d, f), s_in, L._dt(cfg.dtype)),
        "up": L.truncated_normal(ku, (e_pad, d, f), s_in, L._dt(cfg.dtype)),
        "down": L.truncated_normal(kd, (e_pad, f, d), s_out, L._dt(cfg.dtype)),
    }
    if moe.n_shared_experts:
        fs = moe.n_shared_experts * moe.d_shared_expert
        p["shared"] = L.init_glu_mlp(ks, d, fs, cfg.dtype)
        p["shared_gate"] = L.init_dense(ksg, d, 1, cfg.dtype)
    return p


def moe_specs(cfg: ModelConfig):
    p = {
        "router": ("embed", None),
        "gate": ("experts", "embed", "expert_mlp"),
        "up": ("experts", "embed", "expert_mlp"),
        "down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.moe.n_shared_experts:
        p["shared"] = L.glu_mlp_specs()
        p["shared_gate"] = L.dense_specs("embed", None)
    return p


# ---------------------------------------------------------------------------
# routing + local dispatch machinery
# ---------------------------------------------------------------------------


def route(router_w, x, moe: MoEConfig, e_pad: int):
    """x:(T,D) -> (weights (T,k), expert_idx (T,k), router_probs (T,E_pad))."""
    logits = x.astype(jnp.float32) @ router_w  # (T, E_pad)
    if e_pad > moe.n_experts:  # phantom padding experts never win
        mask = jnp.arange(e_pad) < moe.n_experts
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, moe.experts_per_token)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # renormalise top-k
    return w, idx, probs


def load_balance_loss(probs, idx, n_experts: int):
    """Switch-style auxiliary loss (train-time)."""
    e = probs.shape[-1]
    hot = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
    return n_experts * jnp.sum(hot.mean(0) * probs.mean(0))


def dispatch_indices(expert_idx, n_exp: int, cap: int):
    """Group token-slots by expert.

    expert_idx: (T, k) possibly containing out-of-range ids (other shards).
    Returns sorted views: fe (expert id), ft (source token), pos (slot within
    expert), valid (in-range and under capacity), order (perm over T*k).
    """
    t, k = expert_idx.shape
    fe = expert_idx.reshape(-1)
    order = jnp.argsort(fe, stable=True)
    fe_s = fe[order]
    ft_s = jnp.repeat(jnp.arange(t), k)[order]
    starts = jnp.searchsorted(fe_s, jnp.arange(n_exp), side="left")
    pos = jnp.arange(t * k) - starts[jnp.clip(fe_s, 0, n_exp - 1)]
    valid = (fe_s >= 0) & (fe_s < n_exp) & (pos < cap)
    return fe_s, ft_s, pos, valid, order


def capacity(t_tokens: int, k: int, n_buckets: int, factor: float) -> int:
    c = int(t_tokens * k / n_buckets * factor)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _expert_mlp(params, buf, act: str):
    """buf:(E,C,D) -> (E,C,D) through per-expert GLU."""
    a = L.activation(act)
    h = a(jnp.einsum("ecd,edf->ecf", buf, params["gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, params["up"])
    return jnp.einsum("ecf,efd->ecd", h, params["down"])


def _moe_local(params, x, moe: MoEConfig, act: str, e_pad: int, cap: int,
               expert_offset: int = 0, n_local: Optional[int] = None):
    """Single-shard MoE over x:(T,D) for experts [offset, offset+n_local)."""
    n_local = n_local if n_local is not None else e_pad
    t, d = x.shape
    w, idx, probs = route(params["router"], x, moe, e_pad)
    fe, ft, pos, valid, order = dispatch_indices(idx - expert_offset,
                                                 n_local, cap)
    fw = w.reshape(-1)[order]
    buf = jnp.zeros((n_local, cap, d), x.dtype)
    buf = buf.at[jnp.where(valid, fe, n_local),
                 jnp.where(valid, pos, 0)].set(x[ft], mode="drop")
    out_buf = _expert_mlp(params, buf, act)
    y = out_buf.at[jnp.clip(fe, 0, n_local - 1),
                   jnp.clip(pos, 0, cap - 1)].get(mode="clip")
    y = y * (fw * valid)[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[ft].add(y)
    return out, (probs, idx)


# ---------------------------------------------------------------------------
# gather mode (TP-resident, psum combine)
# ---------------------------------------------------------------------------


def moe_gather(params, cfg: ModelConfig, x):
    """x:(B,S,D) sharded on batch, replicated over model -> same out."""
    moe = cfg.moe
    mesh = partition.current_mesh()
    b, s, d = x.shape
    e_pad = params["gate"].shape[0]
    if mesh is None or "model" not in mesh.axis_names:
        cap = capacity(b * s, moe.experts_per_token, e_pad,
                       moe.capacity_factor)
        out, (probs, idx) = _moe_local(params, x.reshape(b * s, d), moe,
                                       cfg.act, e_pad, cap)
        aux = load_balance_loss(probs, idx, moe.n_experts)
        return _add_shared(params, cfg, x, out.reshape(b, s, d)), aux

    n_shards = mesh.shape["model"]
    e_loc = e_pad // n_shards
    data_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def shard_fn(router_w, gate, up, down, xs):
        m = jax.lax.axis_index("model")
        xl = xs.reshape(-1, d)
        cap = capacity(xl.shape[0], moe.experts_per_token, e_pad,
                       moe.capacity_factor)
        p_local = {"router": router_w, "gate": gate, "up": up, "down": down}
        out, (probs, idx) = _moe_local(p_local, xl, moe, cfg.act, e_pad, cap,
                                       expert_offset=m * e_loc,
                                       n_local=e_loc)
        out = jax.lax.psum(out, "model")
        aux = load_balance_loss(probs, idx, moe.n_experts)
        return out.reshape(xs.shape), aux

    batch_spec = P(data_ax if data_ax else None, None, None)
    out, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(params["router"], params["gate"], params["up"], params["down"], x)
    return _add_shared(params, cfg, x, out), aux


def _add_shared(params, cfg: ModelConfig, x, routed):
    if not cfg.moe.n_shared_experts:
        return routed
    shared = L.glu_mlp(params["shared"], x, cfg.act)
    g = jax.nn.sigmoid(L.dense(params["shared_gate"], x).astype(jnp.float32))
    return routed + (shared.astype(jnp.float32) * g).astype(routed.dtype)


# ---------------------------------------------------------------------------
# a2a mode (expert parallel, the paper's alltoallv analogue)
# ---------------------------------------------------------------------------


def moe_a2a(params, cfg: ModelConfig, x, *, axis: str = "model"):
    """x:(B,S,D) with S sharded over ``axis``; explicit all_to_all dispatch."""
    moe = cfg.moe
    mesh = partition.current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return moe_gather(params, cfg, x)
    n_shards = mesh.shape[axis]
    e_pad = params["gate"].shape[0]
    e_loc = e_pad // n_shards
    b, s, d = x.shape
    data_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def shard_fn(router_w, gate, up, down, xs):
        xl = xs.reshape(-1, d)  # (t_loc, d) tokens owned by this shard
        t_loc = xl.shape[0]
        c_send = capacity(t_loc, moe.experts_per_token, n_shards,
                          moe.capacity_factor)
        c_exp = capacity(t_loc * n_shards, moe.experts_per_token, e_pad,
                         moe.capacity_factor)
        w, idx, probs = route(router_w, xl, moe, e_pad)
        dest = idx // e_loc  # destination shard per slot (t_loc, k)
        fe, ft, pos, valid, order = dispatch_indices(dest, n_shards, c_send)
        fw = w.reshape(-1)[order]
        fx = idx.reshape(-1)[order]  # global expert id, sorted by destination
        de = jnp.where(valid, fe, n_shards)
        dp = jnp.where(valid, pos, 0)
        send = jnp.zeros((n_shards, c_send, d), xl.dtype)
        send = send.at[de, dp].set(xl[ft], mode="drop")
        # padding slots carry local-expert id e_loc -> dropped at receiver
        send_eid = jnp.full((n_shards, c_send), e_loc, jnp.int32)
        send_eid = send_eid.at[de, dp].set((fx % e_loc).astype(jnp.int32),
                                           mode="drop")
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0, tiled=False)
        # local expert compute over received slots
        rx = recv.reshape(-1, d)
        p_local = {"gate": gate, "up": up, "down": down}
        fe2, ft2, pos2, valid2, _ = dispatch_indices(
            recv_eid.reshape(-1, 1), e_loc, c_exp)
        buf = jnp.zeros((e_loc, c_exp, d), rx.dtype)
        buf = buf.at[jnp.where(valid2, fe2, e_loc),
                     jnp.where(valid2, pos2, 0)].set(rx[ft2], mode="drop")
        out_buf = _expert_mlp(p_local, buf, cfg.act)
        ry = out_buf.at[jnp.clip(fe2, 0, e_loc - 1),
                        jnp.clip(pos2, 0, c_exp - 1)].get(mode="clip")
        ry = ry * valid2[:, None].astype(ry.dtype)
        back = jnp.zeros((n_shards * c_send, d), ry.dtype).at[ft2].add(ry)
        reply = jax.lax.all_to_all(back.reshape(n_shards, c_send, d),
                                   axis, 0, 0, tiled=False)
        # reply slots line up with send slots -> combine at origin
        y = reply.reshape(n_shards * c_send, d)[de * c_send + dp]
        y = y * (fw * valid)[:, None].astype(y.dtype)
        out = jnp.zeros((t_loc, d), y.dtype).at[ft].add(y)
        aux = load_balance_loss(probs, idx, moe.n_experts)
        return out.reshape(xs.shape), aux

    batch_spec = P(data_ax if data_ax else None, axis, None)
    out, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(params["router"], params["gate"], params["up"], params["down"], x)
    return _add_shared(params, cfg, x, out), aux


def moe_ffn(params, cfg: ModelConfig, x):
    if cfg.moe.dispatch == "a2a":
        return moe_a2a(params, cfg, x)
    return moe_gather(params, cfg, x)


# ---------------------------------------------------------------------------
# dense reference (oracle for tests; no capacity drops)
# ---------------------------------------------------------------------------


def moe_ref_dense(params, cfg: ModelConfig, x):
    """Every token through all its top-k experts via dense one-hot einsum."""
    moe = cfg.moe
    b, s, d = x.shape
    e_pad = params["gate"].shape[0]
    xl = x.reshape(-1, d)
    w, idx, probs = route(params["router"], xl, moe, e_pad)
    hot = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)     # (T,k,E)
    comb = (hot * w[..., None]).sum(1)                      # (T,E)
    per_e = _expert_mlp(params, jnp.broadcast_to(xl, (e_pad, *xl.shape)),
                        cfg.act)                            # (E,T,D)
    out = jnp.einsum("te,etd->td", comb.astype(jnp.float32),
                     per_e.astype(jnp.float32)).astype(xl.dtype)
    return _add_shared(params, cfg, x, out.reshape(b, s, d)), \
        load_balance_loss(probs, idx, moe.n_experts)
