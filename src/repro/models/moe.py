"""Mixture-of-Experts: sort-based capacity dispatch + grouped einsum.

TPU-native formulation (DESIGN.md §2 hardware-adaptation): no [T, E, C]
GShard dispatch tensor (its einsum alone would rival the expert FLOPs at
DeepSeek scale).  Instead:

  1. router top-k -> (expert, weight) per (token, k) slot;
  2. flat sort of T*k assignments by expert id;
  3. scatter into a dense [E, C, D] buffer (capacity C = ceil(T*k/E)*cf,
     overflow dropped — "token dropping", the standard capacity trade);
  4. grouped expert einsum [E,C,D]x[E,D,F] — FLOPs = T*k*cf*D*F*2, i.e.
     model FLOPs times the capacity factor only;
  5. gather back + combine with router weights.

Expert weights shard over the `model` axis: expert dim when divisible
(DeepSeek 160 % 16 == 0 -> true expert parallelism, XLA inserts all_to_all)
else the per-expert FFN dim (Mixtral, 8 experts -> tensor parallel experts).
Shared experts (DeepSeek) are a plain dense MLP added to the MoE output.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    split = cfg.moe_virtual_split
    ev, fv = e * split, f // split
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "experts": {
            # gated (swiglu) expert FFNs, stacked on the (virtual) expert dim
            "w_in": dense_init(ks[1], (ev, d, 2 * fv), cfg.dtype),
            "w_out": dense_init(ks[2], (ev, fv, d), cfg.dtype),
        },
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2 = jax.random.split(ks[3])
        p["shared"] = {
            "w_in": dense_init(k1, (d, 2 * fs), cfg.dtype),
            "w_out": dense_init(k2, (fs, d), cfg.dtype),
        }
    return p


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D]. Returns (out [B,S,D], aux_loss []).

    Dispatch implementation is chosen from the ambient sharding rules:
    when an expert-parallel axis is published (launcher) and the expert
    count is compatible, the shard_map all_to_all path runs (§Perf
    iteration: ~1000x less dispatch traffic than the XLA-resharded dense
    path); otherwise the single-device capacity path below.
    """
    from repro.sharding.context import get_rule

    ep_axis = get_rule("moe_ep_axis")
    mesh = get_rule("mesh")
    if ep_axis is not None and mesh is not None:
        M = mesh.shape[ep_axis]
        ev = cfg.n_experts * cfg.moe_virtual_split
        if ev % M == 0:
            return _moe_ep(params, x, cfg, mesh, ep_axis,
                           get_rule("moe_dp_axes"))
    return _moe_dense(params, x, cfg)


def _route(params, xt, cfg: ModelConfig):
    """Shared routing: top-k over real experts, fanned out to the virtual
    splits.  Returns (idx_v [T, K*split], gate_v, aux)."""
    E, K, split = cfg.n_experts, cfg.top_k, cfg.moe_virtual_split
    T = xt.shape[0]
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)
    if split > 1:
        idx = (idx[..., None] * split + jnp.arange(split)).reshape(T, K * split)
        gate = jnp.repeat(gate, split, axis=-1)
    return idx, gate, aux


def _moe_dense(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    split = cfg.moe_virtual_split
    E = cfg.n_experts * split
    K = cfg.top_k * split
    T = B * S
    xt = x.reshape(T, D)
    idx, gate, aux = _route(params, xt, cfg)

    # ---- sort-based dispatch -------------------------------------------------
    cap = int((T * K / max(E, 1)) * cfg.capacity_factor) + 1
    flat_e = idx.reshape(-1)  # [T*K]
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e)  # stable; groups by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # rank within expert = position - segment start
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")  # [E]
    rank = jnp.arange(T * K) - seg_start[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, E * cap)  # OOB -> dropped

    xe = jnp.zeros((E * cap, D), cfg.dtype).at[slot].set(
        xt[st].astype(cfg.dtype), mode="drop"
    )
    xe = xe.reshape(E, cap, D)

    # ---- grouped expert FFN ----------------------------------------------------
    h = jnp.einsum("ecd,edf->ecf", xe, params["experts"]["w_in"])
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["experts"]["w_out"])

    # ---- combine ---------------------------------------------------------------
    ye_flat = ye.reshape(E * cap, D)
    gathered = ye_flat[jnp.minimum(slot, E * cap - 1)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    out = jnp.zeros((T, D), jnp.float32).at[st].add(
        gathered.astype(jnp.float32) * sg[:, None]
    )
    out = out.astype(x.dtype).reshape(B, S, D)

    if "shared" in params:
        out = out + _shared_mlp(params["shared"], x)
    return out, aux


def _shared_mlp(p, x):
    hs = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    g, u = jnp.split(hs, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_out"])


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map all_to_all) — §Perf
# ---------------------------------------------------------------------------
#
# The GraSorw idea at MoE scale (DESIGN.md §2): routed tokens are "walks",
# experts are "blocks"; instead of letting every rank fetch every token
# (XLA's dense resharding = the light random I/O of the paper), tokens are
# *bucketed by destination expert* and exchanged in one sequential
# all_to_all per direction — the bucket I/O of §4.3.
#
# Layout trick: bins are EXPERT-major, [E_v, cap, D]; all_to_all over the
# leading axis hands each rank exactly its experts' tokens in a contiguous
# block, so the local compute is one grouped einsum, no second shuffle.
#
# When the mesh axis is wider than the expert count (mixtral: 8 experts,
# 16-way axis), each expert's FFN is split column-wise into M/E *virtual
# experts* (exact for gated MLPs: silu(x g_h) u_h sums over halves), every
# assignment fans out to all halves, and the combine sums them.

def _moe_ep(params, x, cfg: ModelConfig, mesh, ep_axis: str, dp_axes):
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    split = cfg.moe_virtual_split
    E_v = cfg.n_experts * split
    K_v = cfg.top_k * split
    M = mesh.shape[ep_axis]
    epr = E_v // M  # (virtual) experts per rank

    s_ax = ep_axis if S % M == 0 else None
    b_ax = dp_axes if (dp_axes and B % _axes_size(mesh, dp_axes) == 0) else None
    xspec = P(b_ax, s_ax, None)
    wspec = P(ep_axis, None, None)

    def local(xl, w_in_l, w_out_l, router_w):
        """Per-shard: route -> expert-major bins -> a2a -> grouped einsum ->
        a2a back -> combine.  xl: [Bl, Sl, D]; w_*_l: [epr, ...]."""
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, D)
        idx_v, gate_v, aux = _route({"router": router_w}, xt, cfg)

        A = T * K_v
        cap = max(int(A / E_v * cfg.capacity_factor) + 1, 4)
        flat_e = idx_v.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), K_v)
        order = jnp.argsort(flat_e)
        se, st = flat_e[order], flat_t[order]
        seg = jnp.searchsorted(se, jnp.arange(E_v), side="left")
        rank = jnp.arange(A) - seg[se]
        keep = rank < cap
        slot = jnp.where(keep, se * cap + rank, E_v * cap)  # OOB -> dropped
        bins = jnp.zeros((E_v * cap, D), xl.dtype).at[slot].set(
            xt[st].astype(xl.dtype), mode="drop"
        ).reshape(E_v, cap, D)

        # ---- bucket exchange: one sequential a2a each way (§4.3 analogue)
        recv = jax.lax.all_to_all(
            bins, ep_axis, split_axis=0, concat_axis=0, tiled=True
        )
        # recv rows are source-rank-major: [M, epr, cap, D]
        toks = recv.reshape(M, epr, cap, D).transpose(1, 0, 2, 3)
        toks = toks.reshape(epr, M * cap, D)
        h = jnp.einsum("ecd,edf->ecf", toks, w_in_l)
        g, u = jnp.split(h, 2, axis=-1)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_out_l)
        back = ye.reshape(epr, M, cap, D).transpose(1, 0, 2, 3)
        back = back.reshape(E_v, cap, D)
        ret = jax.lax.all_to_all(
            back, ep_axis, split_axis=0, concat_axis=0, tiled=True
        )  # [E_v, cap, D]: my tokens' outputs, expert-major

        ret_flat = ret.reshape(E_v * cap, D)
        got = ret_flat[jnp.minimum(slot, E_v * cap - 1)]
        got = jnp.where(keep[:, None], got, 0)
        sg = gate_v.reshape(-1)[order]
        out = jnp.zeros((T, D), jnp.float32).at[st].add(
            got.astype(jnp.float32) * sg[:, None]
        )
        for ax in mesh.axis_names:
            aux = jax.lax.pmean(aux, ax)
        return out.astype(xl.dtype).reshape(Bl, Sl, D), aux

    out, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, wspec, wspec, P(None, None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, params["experts"]["w_in"], params["experts"]["w_out"],
      params["router"].astype(jnp.float32))
    if "shared" in params:
        out = out + _shared_mlp(params["shared"], x)
    return out, aux


def _axes_size(mesh, axes):
    import numpy as np

    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))
