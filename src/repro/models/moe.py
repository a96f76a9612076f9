"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, shared experts.

Dispatch is the capacity-buffer formulation (GShard-style, sort-free): each
(token, k) assignment gets a slot in a per-expert buffer [E, C, D]; expert
FFNs run as one grouped einsum over E; outputs gather back weighted. Under
GSPMD the E axis is sharded over the ``model`` mesh axis (expert
parallelism) and the scatter/gather lower to cross-shard collectives; the
shard_map all-to-all variant is evaluated in EXPERIMENTS §Perf.

Routing: softmax → top-k, renormalized (DeepSeek-V3 style), plus the
standard load-balance auxiliary loss. The dense path is dropless (buffer
capacity = token count, so outputs are batch-composition-independent — see
``moe_ffn_dense``); the EP path keeps bounded per-rank capacity, where
over-capacity assignments drop (their combine weight zeroes) to cap the
all_to_all buffer sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, split
from repro.models.ffn import ffn, init_ffn


def init_moe(key, cfg):
    e, d = cfg.moe, cfg.d_model
    ks = split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e.n_experts, jnp.float32),
        "w_gate": dense_init(ks[1], e.n_experts * d, e.d_expert).reshape(
            e.n_experts, d, e.d_expert),
        "w_up": dense_init(ks[2], e.n_experts * d, e.d_expert).reshape(
            e.n_experts, d, e.d_expert),
        "w_down": dense_init(ks[3], e.n_experts * e.d_expert, d).reshape(
            e.n_experts, e.d_expert, d),
    }
    if e.n_shared:
        p["shared"] = init_ffn(ks[4], d, e.n_shared * e.d_expert)
    return p


def moe_ffn(params, cfg, x):
    """x: [B, S, D] → (y, aux_loss). Picks the EP all-to-all path when the
    launcher enabled sharding hints and the expert count divides the model
    axis (§Perf iteration 1); otherwise the GSPMD capacity-buffer path."""
    from repro.parallel import hints

    e = cfg.moe
    if hints.enabled():
        mesh = hints.mesh()
        if mesh is not None:
            tp = mesh.shape.get(hints.axes("tp"), 1)
            if tp > 1 and e.n_experts % tp == 0 and x.shape[1] % tp == 0:
                return moe_ffn_ep(params, cfg, x, mesh)
    return moe_ffn_dense(params, cfg, x)


def moe_ffn_dense(params, cfg, x):
    """Einsum/scatter dispatch (single-device & fallback path) — dropless.

    The buffer capacity is the token count itself, so no assignment ever
    drops and each token's output is a pure function of (token, weights).
    That invariant is what makes serving correct: the same token produces
    bit-identical results in a full-sequence train forward, a (T-1)-token
    prefill, and a 1-token decode step. A token-count-scaled capacity
    (``int(t·k/E·cf)+1``) breaks it two ways: the cap rounds differently per
    call so prefill drops assignments the full forward keeps (stale KV
    cache), and at decode t is so small the cap collapses to 1, dropping
    live assignments outright. Both modeled MoE families are dropless in
    production (DeepSeek-V3 drops no tokens; DBRX is dropless MegaBlocks).
    Bounded-capacity semantics live on in ``moe_ffn_ep``, where capacity
    bounds the all_to_all buffers — a real network constraint.
    """
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    top_w, top_i = jax.lax.top_k(probs, e.top_k)               # [T, k]
    top_w = top_w / jnp.maximum(
        jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # load-balance aux (Switch): E * sum_e f_e * P_e
    f = jnp.mean(jax.nn.one_hot(top_i[:, 0], e.n_experts, dtype=jnp.float32),
                 axis=0)
    aux = e.n_experts * jnp.sum(f * jnp.mean(probs, axis=0)) \
        * e.router_aux_weight

    # ---- dropless dispatch -------------------------------------------------
    # top-k indices are distinct per token, so per-expert load ≤ t: a t-slot
    # buffer can never overflow (costs k/E·cf× more slots than a capacity
    # buffer — the price of batch-composition-independent outputs).
    cap = t
    flat_e = top_i.reshape(-1)                                  # [T*k]
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), e.top_k)

    # slot within expert = how many earlier assignments chose the same expert
    onehot = jax.nn.one_hot(flat_e, e.n_experts, dtype=jnp.int32)  # [Tk, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot                 # exclusive
    slot = jnp.sum(pos_in_e * onehot, axis=-1)                     # [Tk]

    buf = jnp.zeros((e.n_experts, cap, d), x.dtype)
    buf = buf.at[flat_e, slot].set(xf[flat_t])

    # ---- grouped expert FFN ------------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))

    # ---- combine -----------------------------------------------------------
    gathered = y_e[flat_e, slot]                                   # [Tk, D]
    w = flat_w.astype(x.dtype)
    y = jnp.sum((gathered * w[:, None]).reshape(t, e.top_k, d), axis=1)

    if e.n_shared:
        y = y + ffn(params["shared"], x).reshape(t, d)
    return y.reshape(b, s, d), aux


# ===================================================================== EP
def moe_ffn_ep(params, cfg, x, mesh):
    """Expert-parallel MoE via shard_map + all_to_all (§Perf iteration 1).

    The GSPMD capacity-buffer path scatters tokens into EXPERT-sharded
    buffers straight from TOKEN-sharded activations — on the 671B config the
    partitioner materializes a [T·k, E] cumsum and reduces dispatch tensors
    across the model axis: 16.5 TB/chip of all-reduce wire bytes (measured,
    EXPERIMENTS §Perf). Here the exchange is explicit and minimal:

      per device: local router → top-k → bucket by destination EP rank
      (exclusive-cumsum slotting, LOCAL [T_loc·k, M] only) → one all_to_all
      carrying each token once per chosen expert → local grouped FFN over
      E/M experts → reverse all_to_all → weighted combine.

    Drop semantics: fixed per-(source, dest) capacity, like production
    capacity-factor routing (slightly different drop set than the global-
    capacity dense path; equal in expectation).
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel import hints

    e = cfg.moe
    b, s, d = x.shape
    dp_axes = hints._STATE["dp"]
    tp_axis = hints.axes("tp")
    m = mesh.shape[tp_axis]
    e_loc = e.n_experts // m
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    # tokens are sharded over BOTH the data axes (batch) and the model axis
    # (sequence): every EP rank routes a DISTINCT token slice — with
    # model-replicated tokens the exchange and expert compute would be
    # tp-times redundant (measured: +171%% compute, first attempt, §Perf)
    t_loc = (b // dp_total) * (s // m)
    cap = int(t_loc * e.top_k / m * e.capacity_factor) + 1
    r_tot = m * (cap + 1)
    cap2 = int(r_tot / e_loc * e.capacity_factor) + 1

    def local(xb, router_w, w_gate, w_up, w_down):
        # xb: [B_loc, S_loc, D]; experts already sliced to [E_loc, D, F]
        xf = xb.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, e.top_k)
        top_w = top_w / jnp.maximum(
            jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

        f = jnp.mean(jax.nn.one_hot(top_i[:, 0], e.n_experts,
                                    dtype=jnp.float32), axis=0)
        aux = e.n_experts * jnp.sum(f * jnp.mean(probs, axis=0)) \
            * e.router_aux_weight
        aux = jax.lax.pmean(jax.lax.pmean(aux, tp_axis), dp_axes)

        # ---- bucket by destination EP rank (all indices LOCAL) ----------
        flat_e = top_i.reshape(-1)                        # [A]
        flat_w = top_w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc), e.top_k)
        dest = flat_e // e_loc                            # [A] → rank
        oh = jax.nn.one_hot(dest, m, dtype=jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) - oh)
        slot = jnp.take_along_axis(pos, dest[:, None], 1)[:, 0]
        keep = slot < cap
        slot = jnp.where(keep, slot, cap)

        send_x = jnp.zeros((m, cap + 1, d), xb.dtype)
        send_x = send_x.at[dest, slot].set(xf[flat_t])
        send_id = jnp.full((m, cap + 1), -1, jnp.int32)
        send_id = send_id.at[dest, slot].set(
            jnp.where(keep, flat_e % e_loc, -1))

        recv_x = jax.lax.all_to_all(send_x, tp_axis, 0, 0)
        recv_id = jax.lax.all_to_all(send_id, tp_axis, 0, 0)

        # ---- local grouped FFN over E_loc experts -----------------------
        rx = recv_x.reshape(r_tot, d)
        re = recv_id.reshape(r_tot)
        valid = re >= 0
        rec = jnp.clip(re, 0, e_loc - 1)
        oh2 = jax.nn.one_hot(rec, e_loc, dtype=jnp.int32) * valid[:, None]
        pos2 = (jnp.cumsum(oh2, axis=0) - oh2)
        slot2 = jnp.take_along_axis(pos2, rec[:, None], 1)[:, 0]
        keep2 = jnp.logical_and(slot2 < cap2, valid)
        slot2 = jnp.where(keep2, slot2, cap2)

        buf = jnp.zeros((e_loc, cap2 + 1, d), xb.dtype)
        buf = buf.at[rec, slot2].set(rx)
        gg = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(xb.dtype))
        uu = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(xb.dtype))
        hh = jax.nn.silu(gg) * uu
        y_e = jnp.einsum("ecf,efd->ecd", hh, w_down.astype(xb.dtype))

        y_recv = y_e[rec, slot2] * keep2[:, None].astype(xb.dtype)
        y_back = jax.lax.all_to_all(
            y_recv.reshape(m, cap + 1, d), tp_axis, 0, 0)

        gathered = y_back[dest, slot] * keep[:, None].astype(xb.dtype)
        y = jnp.sum((gathered * flat_w[:, None].astype(xb.dtype))
                    .reshape(t_loc, e.top_k, d), axis=1)
        return y.reshape(xb.shape), aux

    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    # y is replicated over the model axis by construction (each rank gets
    # its own tokens back from the reverse all_to_all) — the static VMA
    # checker can't see through the round-trip, hence check_vma=False.
    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, tp_axis, None), P(None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None)),
        out_specs=(P(dp, tp_axis, None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])

    if e.n_shared:
        y = y + ffn(params["shared"], x)
    return y, aux
