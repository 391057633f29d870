"""Mixture-of-Experts FFN with two dispatch implementations.

``dense``  — one-hot capacity einsum dispatch (GShard-style). Simple and
             exactly differentiable; used for smoke tests and decode steps
             (tiny token counts).
``a2a``    — production path: ``shard_map`` over the full mesh with explicit
             ``lax.all_to_all`` exchanges. Tokens are sharded over every mesh
             axis; experts are sharded over 'model' (expert parallelism).
             Deterministic collective schedule, scatter-based dispatch (no
             one-hot matmul, so HLO FLOPs stay honest for the roofline).

Both paths use capacity-factor token dropping (dropped tokens contribute
zero; arctic's dense residual branch keeps them on the gradient path).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.sharding import active_rules
from repro.models.layers import ParamTable, f32


def moe_table(cfg, prefix, L) -> ParamTable:
    m = cfg.moe
    d, ff, E = cfg.d_model, m.d_ff_expert, m.n_experts
    s = 0.02
    gated = cfg.mlp_variant in ("swiglu", "geglu")
    t = {
        prefix + "/router": ((L, d, E), ("layers", "dmodel", None), ("normal", s)),
        prefix + "/w_up": ((L, E, d, ff), ("layers", "experts", "fsdp", None), ("normal", s)),
        prefix + "/w_down": ((L, E, ff, d), ("layers", "experts", None, "fsdp"), ("normal", s)),
    }
    if gated:
        t[prefix + "/w_gate"] = ((L, E, d, ff), ("layers", "experts", "fsdp", None), ("normal", s))
    return t


def _expert_mlp(cfg, p, h):
    """h: [E, C, d] -> [E, C, d] batched over experts (bf16 dots: the
    expert weights arrive through an fsdp all-gather in this dtype)."""
    up = jnp.einsum("ecd,edf->ecf", h, p["w_up"].astype(h.dtype))
    if cfg.mlp_variant in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(h.dtype))
        gf = g.astype(f32)
        act = (jax.nn.silu(gf) if cfg.mlp_variant == "swiglu"
               else jax.nn.gelu(gf, approximate=True)).astype(h.dtype)
        hidden = act * up
    elif cfg.mlp_variant == "relu2":
        hidden = jnp.square(jax.nn.relu(up))
    else:
        hidden = jax.nn.gelu(up.astype(f32), approximate=True).astype(h.dtype)
    return jnp.einsum("ecf,efd->ecd", hidden.astype(h.dtype),
                      p["w_down"].astype(h.dtype))


def _route(cfg, p, x2d):
    """x2d: [T, d] -> (weights [T, K], idx [T, K], aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d, p["router"].astype(x2d.dtype),
                        preferred_element_type=f32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # GShard aux loss: E * mean(frac_tokens_e * mean_prob_e)
    E = m.n_experts
    onehot = jax.nn.one_hot(idx[:, 0], E, dtype=f32)  # count top-1 choice
    aux = E * jnp.mean(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return w.astype(f32), idx, aux


def _positions_in_expert(idx, E):
    """idx: [T, K] expert choices -> slot position of each (t, k) within its
    expert, counted in (t, k) order. [T, K] int32."""
    T, K = idx.shape
    flat = idx.reshape(-1)  # [T*K], (t-major, k-minor) order
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # [T*K, E]
    pos = jnp.cumsum(onehot, axis=0) - 1  # inclusive -> 0-based
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    return pos.reshape(T, K)


def moe_dense(cfg, p, x):
    """One-hot capacity dispatch. x: [B, S, d] (or [T, d])."""
    m = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    T = x2d.shape[0]
    E, K = m.n_experts, m.top_k
    cap = max(1, int(T * K * m.capacity_factor / E))
    w, idx, aux = _route(cfg, p, x2d)
    pos = _positions_in_expert(idx, E)
    keep = pos < cap
    # dispatch: [T, K] scatter into [E, cap, d]
    buf = jnp.zeros((E, cap, x2d.shape[1]), x.dtype)
    t_idx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K))
    e_flat = jnp.where(keep, idx, E)      # out-of-range rows are dropped
    buf = buf.at[e_flat.reshape(-1), jnp.where(keep, pos, 0).reshape(-1)].add(
        jnp.repeat(x2d, K, axis=0).reshape(T * K, -1) *
        keep.reshape(T * K, 1).astype(x.dtype),
        mode="drop")
    y_buf = _expert_mlp(cfg, p, buf)
    # combine: gather back each (t, k) slot
    gathered = y_buf[e_flat.reshape(-1), jnp.where(keep, pos, 0).reshape(-1)]
    gathered = gathered * keep.reshape(T * K, 1).astype(x.dtype)
    y = jnp.sum((gathered.reshape(T, K, -1) * w[..., None].astype(x.dtype)),
                axis=1)
    del t_idx
    return y.reshape(shape), aux


def moe_a2a(cfg, p, x, sp: bool):
    """Expert-parallel MoE via shard_map + all_to_all. x: [B, S, d].

    sp=True: the caller's residual stream is sequence-parallel — tokens
    arrive already split over ('batch' x data-axes, 'seq' x model); the
    shard_map boundary is a no-op reshard and the only collectives are the
    two dispatch/return all_to_alls.
    sp=False (jamba: recurrence forbids seq sharding): tokens arrive
    data-sharded; the model-axis seq split/all-gather happens inside.
    """
    rules = active_rules()
    mesh = rules.mesh
    m = cfg.moe
    B, S, d = x.shape
    axes = tuple(mesh.axis_names)          # e.g. ('pod', 'data', 'model')
    data_axes = tuple(a for a in axes if a != "model")
    Pmodel = mesh.shape["model"]
    E = m.n_experts
    E_loc = E // Pmodel
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    t_loc = (B // n_data) * (S // Pmodel)
    # per-source-device, per-expert capacity
    cap = max(1, int(-(-t_loc * m.top_k * m.capacity_factor // E)))
    K = m.top_k

    def block(x_blk, pp):
        # x_blk: [B_loc, S_loc(, /Pmodel if sp), d]
        if not sp:
            midx = lax.axis_index("model")
            s_loc = x_blk.shape[1] // Pmodel
            xs = lax.dynamic_slice_in_dim(x_blk, midx * s_loc, s_loc, axis=1)
        else:
            xs = x_blk
        tok = xs.reshape(-1, d)
        w, idx, aux = _route(cfg, pp, tok)
        pos = _positions_in_expert(idx, E)
        keep = pos < cap
        peer = idx // E_loc
        e_loc = idx % E_loc
        # send buffer [Pmodel, E_loc, cap, d]
        send = jnp.zeros((Pmodel, E_loc, cap, d), tok.dtype)
        flat_keep = keep.reshape(-1)
        send = send.at[
            peer.reshape(-1), e_loc.reshape(-1),
            jnp.where(flat_keep, pos.reshape(-1), 0)].add(
            jnp.repeat(tok, K, axis=0) * flat_keep[:, None].astype(tok.dtype),
            mode="drop")
        # exchange over the model axis: recv[src, e_loc, cap, d]
        recv = lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=False)
        h = recv.transpose(1, 0, 2, 3).reshape(E_loc, Pmodel * cap, d)
        y = _expert_mlp(cfg, pp, h)
        y = y.reshape(E_loc, Pmodel, cap, d).transpose(1, 0, 2, 3)
        back = lax.all_to_all(y, "model", split_axis=0, concat_axis=0,
                              tiled=False)
        # combine at the source: same (peer, e_loc, pos) slots
        gathered = back[peer.reshape(-1), e_loc.reshape(-1),
                        jnp.where(flat_keep, pos.reshape(-1), 0)]
        gathered = gathered * flat_keep[:, None].astype(tok.dtype)
        y_tok = jnp.sum(gathered.reshape(-1, K, d) *
                        w[..., None].astype(tok.dtype), axis=1)
        y_tok = y_tok.reshape(xs.shape)
        if not sp:
            # reassemble the full sequence from the model-axis splits
            y_tok = lax.all_gather(y_tok, "model", axis=1, tiled=True)
        # aux loss: average over all devices
        aux = lax.pmean(aux, axes)
        return y_tok, aux

    gated = cfg.mlp_variant in ("swiglu", "geglu")
    if sp:
        tok_spec = P(data_axes, "model", None)
    else:
        tok_spec = P(data_axes, None, None)
    pp = {"router": p["router"], "w_up": p["w_up"], "w_down": p["w_down"]}
    pp_specs = {"router": P(), "w_up": P("model"), "w_down": P("model")}
    if gated:
        pp["w_gate"] = p["w_gate"]
        pp_specs["w_gate"] = P("model")
    fn = jax.shard_map(
        block, mesh=mesh, in_specs=(tok_spec, pp_specs),
        out_specs=(tok_spec, P()), check_vma=False)
    y, aux = fn(x, pp)
    return y, aux


def moe_ffn(cfg, p, x, kind: str, sp: bool = False):
    """Dispatch-implementation selector."""
    m = cfg.moe
    rules = active_rules()
    B, S = x.shape[0], x.shape[1]
    usable_a2a = False
    if (rules is not None and "model" in rules.mesh.shape
            and rules.mesh.shape["model"] > 1
            and kind in ("train", "prefill")
            and m.n_experts % rules.mesh.shape["model"] == 0):
        Pm = rules.mesh.shape["model"]
        n_data = rules.mesh.size // Pm
        usable_a2a = (B % n_data == 0) and (S % Pm == 0)
        sp = sp and rules.table.get("seq_sp") is not None
    if usable_a2a:
        return moe_a2a(cfg, p, x, sp)
    return moe_dense(cfg, p, x)
