"""Mixture-of-Experts layer (granite-moe, grok-1).

Top-k routing with a sort-based, all-gather-free dispatch that is fully
gather-based (no scatters — friendlier to GSPMD sharding propagation):

  1. replicate each token k times, tag with its routed expert id;
  2. sort the M = N*k rows by expert id;
  3. expert buffers [E, cap, d] are *gathers* from the sorted rows
     (slot (e, c) <- sorted row  offsets[e] + c, zero-masked past counts);
  4. batched expert FFN over the stacked buffers (one einsum on the MXU);
  5. each sorted row gathers its output back from its buffer slot,
     unsorts, and the k copies combine with router weights.

Tokens overflowing an expert's capacity are dropped (standard
capacity-factor semantics); cap = ceil(N * k / E * capacity_factor).

Sharding: two strategies, per config —
  * EP  ("expert"): buffers [E, cap, d] sharded E over the `model` axis
    (requires E % axis == 0; granite's 32 experts / 16).  The
    token->expert reshard is the all_to_all the roofline tracks.
  * TP  ("tensor"): experts replicated, each expert's d_ff sharded over
    `model` (grok's 8 experts on a 16-way axis).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init


def moe_init(key, d: int, d_ff: int, n_experts: int, *,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    sf = d_ff ** -0.5
    return {
        "router": dense_init(ks[0], d, n_experts, dtype=jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (n_experts, d, d_ff)) * s).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (n_experts, d, d_ff)) * s).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (n_experts, d_ff, d)) * sf).astype(dtype),
    }


def moe_apply(
    p: Params,
    x: jax.Array,            # [B, T, D]
    *,
    top_k: int,
    n_experts: int,
    capacity_factor: float = 1.25,
    router_z_coef: float = 1e-3,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, T, D], aux_loss scalar: load-balance + router-z)."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    e = n_experts

    logits = (xf.astype(jnp.float32) @ p["router"]["w"])     # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, sel = jax.lax.top_k(probs, top_k)                # [N, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # aux losses (Switch-style load balance + router z)
    me = jnp.mean(probs, axis=0)
    ce_frac = jnp.mean(
        (jax.nn.one_hot(sel, e).sum(axis=1)).astype(jnp.float32), axis=0
    ) / top_k
    aux = e * jnp.sum(me * ce_frac)
    aux = aux + router_z_coef * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)

    m = n * top_k
    cap = int(-(-(n * top_k * capacity_factor) // e))        # ceil
    cap = max(8, min(cap, m))

    eid = sel.reshape(m)                                     # [M]
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)  # [M]
    order = jnp.argsort(eid, stable=True)
    s_eid = eid[order]
    s_tok = tok[order]
    counts = jnp.bincount(s_eid, length=e)                   # [E]
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])     # [E]
    pos = jnp.arange(m, dtype=jnp.int32) - offsets[s_eid]    # rank in expert

    # dispatch: buffer slot (e, c) <- sorted row offsets[e] + c
    slot_rows = offsets[:, None] + jnp.arange(cap)[None, :]  # [E, cap]
    slot_valid = jnp.arange(cap)[None, :] < jnp.minimum(counts, cap)[:, None]
    slot_rows = jnp.clip(slot_rows, 0, m - 1)
    buf_tok = s_tok[slot_rows]                               # [E, cap]
    xb = xf[buf_tok] * slot_valid[..., None].astype(xf.dtype)  # [E, cap, D]

    # batched expert FFN (SwiGLU)
    up = jnp.einsum("ecd,edf->ecf", xb, p["w_up"].astype(xb.dtype))
    gate = jnp.einsum("ecd,edf->ecf", xb, p["w_gate"].astype(xb.dtype))
    hidden = jax.nn.silu(gate) * up
    yb = jnp.einsum("ecf,efd->ecd", hidden, p["w_down"].astype(xb.dtype))

    # combine: each sorted row reads back its slot (dropped rows read 0)
    in_cap = pos < cap
    flat_slot = jnp.clip(s_eid * cap + pos, 0, e * cap - 1)
    y_rows = yb.reshape(e * cap, d)[flat_slot]
    y_rows = y_rows * in_cap[:, None].astype(y_rows.dtype)
    # unsort back to [N, k, D] and combine with gate weights
    inv = jnp.argsort(order, stable=True)
    y_nk = y_rows[inv].reshape(n, top_k, d)
    y = jnp.einsum("nkd,nk->nd", y_nk.astype(jnp.float32),
                   gate_w).astype(x.dtype)
    return y.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map + all_to_all
# ---------------------------------------------------------------------------

def moe_apply_ep(
    p: Params,
    x: jax.Array,            # [B, T, D], sharded as ``act_sharding``
    *,
    top_k: int,
    n_experts: int,
    act_sharding,            # NamedSharding of x (carries the mesh)
    capacity_factor: float = 1.25,
    router_z_coef: float = 1e-3,
) -> tuple[jax.Array, jax.Array]:
    """EP MoE with an EXPLICIT all_to_all token exchange over `model`.

    The gather-based dispatch above is correct under GSPMD but lowers to a
    full-buffer masked-sum all-reduce when tokens are data-sharded and
    buffers expert-sharded (measured: 34 GB/layer wire on granite
    prefill_32k).  This path routes tokens with the same capacity-grouped
    all_to_all the distributed PiPNN build uses for candidate edges —
    wire cost is k * token bytes instead of the full expert buffers.
    """
    from jax.sharding import PartitionSpec as PS

    from repro.distributed.routing import group_by_capacity

    mesh = act_sharding.mesh
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1 \
            or n_experts % mesh.shape["model"] != 0:
        return moe_apply(p, x, top_k=top_k, n_experts=n_experts,
                         capacity_factor=capacity_factor,
                         router_z_coef=router_z_coef)
    sm = mesh.shape["model"]
    e_loc = n_experts // sm
    x_spec = act_sharding.spec
    w_spec = PS("model", None, None)
    d = x.shape[-1]

    # static local shapes for capacity sizing
    def dimsize(size, entry):
        if entry is None:
            return size
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        import numpy as _np
        return size // int(_np.prod([mesh.shape[a] for a in axes]))

    b_loc = dimsize(x.shape[0], x_spec[0] if len(x_spec) > 0 else None)
    t_loc = dimsize(x.shape[1], x_spec[1] if len(x_spec) > 1 else None)
    n_loc = b_loc * t_loc
    cap_send = -(-n_loc * top_k * int(capacity_factor * 4) // (4 * sm))
    cap_send = max(8, -(-cap_send // 8) * 8)
    cap_e = -(-n_loc * sm * top_k * int(capacity_factor * 4) // (4 * n_experts))
    cap_e = max(8, -(-cap_e // 8) * 8)

    def body(xl, router_w, w_gate, w_up, w_down):
        bl, tl, _ = xl.shape
        n = bl * tl
        xf = xl.reshape(n, d)
        logits = xf.astype(jnp.float32) @ router_w          # [n, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, sel = jax.lax.top_k(probs, top_k)           # [n, k]
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        axes = tuple(mesh.axis_names)
        me = jax.lax.pmean(jnp.mean(probs, axis=0), axes)
        ce_frac = jax.lax.pmean(jnp.mean(
            jax.nn.one_hot(sel, n_experts).sum(axis=1), axis=0), axes) / top_k
        aux = n_experts * jnp.sum(me * ce_frac)
        aux = aux + router_z_coef * jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, -1) ** 2), axes)

        m = n * top_k
        eid = sel.reshape(m)
        owner = eid // e_loc                                 # model shard
        slot = jnp.arange(m, dtype=jnp.int32)
        xrep = jnp.repeat(xf, top_k, axis=0)
        (s_x, s_eid, s_slot), s_ok = group_by_capacity(
            owner, jnp.ones((m,), bool), sm, cap_send,
            [xrep, eid, slot])
        a2a = functools.partial(jax.lax.all_to_all, axis_name="model",
                                split_axis=0, concat_axis=0, tiled=True)
        r_x, r_eid = a2a(s_x), a2a(s_eid)
        r_ok = a2a(s_ok)
        nr = sm * cap_send
        r_x = r_x.reshape(nr, d)
        r_eid = r_eid.reshape(nr)
        r_ok = r_ok.reshape(nr)
        # regroup by LOCAL expert
        lex = jnp.where(r_ok, r_eid % e_loc, e_loc)
        (b_x, b_src), b_ok = group_by_capacity(
            lex, r_ok, e_loc, cap_e, [r_x, jnp.arange(nr, dtype=jnp.int32)])
        b_x = jnp.where(b_ok[..., None], b_x, 0.0)

        up = jnp.einsum("ecd,edf->ecf", b_x, w_up.astype(b_x.dtype))
        gate = jnp.einsum("ecd,edf->ecf", b_x, w_gate.astype(b_x.dtype))
        yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                        w_down.astype(b_x.dtype))            # [e_loc,cap_e,d]

        # un-group back to recv-buffer order, then a2a home
        y_r = jnp.zeros((nr, d), jnp.float32)
        y_r = y_r.at[jnp.where(b_ok, b_src, nr).reshape(-1)].set(
            yb.reshape(-1, d), mode="drop")
        y_home = a2a(y_r.reshape(sm, cap_send, d))           # my send layout
        y_flat = jnp.zeros((m, d), jnp.float32)
        y_flat = y_flat.at[jnp.where(s_ok, s_slot, m).reshape(-1)].set(
            y_home.reshape(-1, d), mode="drop")
        y = jnp.einsum("nkd,nk->nd", y_flat.reshape(n, top_k, d), gate_w)
        return y.reshape(bl, tl, d).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, PS(), w_spec, w_spec, w_spec),
        out_specs=(x_spec, PS()), check_vma=False,
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
