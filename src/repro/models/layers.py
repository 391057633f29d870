"""Shared neural-net layers: norms, RoPE, attention, MLP variants, embeddings.

All layers are pure functions over flat ``{name: array}`` param dicts. Param
shapes + logical sharding axes come from declarative *param tables* so the
dry-run can build ``ShapeDtypeStruct`` pytrees without allocating anything.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding import tag

f32 = jnp.float32

# ---------------------------------------------------------------------------
# Param tables: name -> (shape, logical_axes, init)
#   init: ('normal', stddev) | ('zeros',) | ('ones',) | ('const', v) |
#         ('uniform', lo, hi)
# ---------------------------------------------------------------------------

ParamTable = Dict[str, Tuple[Tuple[int, ...], Tuple, Tuple]]


def table_struct(table: ParamTable, dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    return {k: jax.ShapeDtypeStruct(shape, dtype) for k, (shape, _, _) in table.items()}


def table_axes(table: ParamTable) -> Dict[str, Tuple]:
    return {k: axes for k, (_, axes, _) in table.items()}


def table_init(table: ParamTable, key, dtype) -> Dict[str, jax.Array]:
    out = {}
    keys = jax.random.split(key, len(table))
    for k_rng, (name, (shape, _, init)) in zip(keys, sorted(table.items())):
        kind = init[0]
        if kind == "normal":
            arr = jax.random.normal(k_rng, shape, f32) * init[1]
        elif kind == "zeros":
            arr = jnp.zeros(shape, f32)
        elif kind == "ones":
            arr = jnp.ones(shape, f32)
        elif kind == "const":
            arr = jnp.full(shape, init[1], f32)
        elif kind == "uniform":
            arr = jax.random.uniform(k_rng, shape, f32, init[1], init[2])
        else:
            raise ValueError(kind)
        out[name] = arr.astype(dtype)
    return out


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale):
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * lax.rsqrt(var + 1e-6)) * (1.0 + scale.astype(f32))).astype(x.dtype)


def layernorm(x, scale, bias):
    xf = x.astype(f32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + 1e-5)
    return (y * (1.0 + scale.astype(f32)) + bias.astype(f32)).astype(x.dtype)


def norm(cfg, params, prefix, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, params[prefix + "/scale"])
    return layernorm(x, params[prefix + "/scale"], params[prefix + "/bias"])


def norm_table(cfg, prefix, stacked_layers=0) -> ParamTable:
    d = cfg.d_model
    lead = (stacked_layers,) if stacked_layers else ()
    lax_ = ("layers",) if stacked_layers else ()
    t = {prefix + "/scale": (lead + (d,), lax_ + ("dmodel",), ("zeros",))}
    if cfg.norm == "layernorm":
        t[prefix + "/bias"] = (lead + (d,), lax_ + ("dmodel",), ("zeros",))
    return t


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta):
    """x: [..., S, H, hd]; positions: [S] or [B, S] (broadcast over heads)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=f32) / half)
    ang = positions.astype(f32)[..., None] * freq  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

_NEG = -1e30


def _attn_block_size(B, S, H, hd):
    """Pick a block size so one score block ([B_loc, qb, H_loc, kb] f32)
    stays under ~256 MB per device, given the active sharding rules."""
    from repro.sharding import active_rules
    rules = active_rules()
    b_sh = h_sh = 1
    if rules is not None:
        d_size = rules.axis_size(rules.table.get("batch"))
        m_size = rules.axis_size(rules.table.get("heads"))
        b_sh = d_size if B % max(d_size, 1) == 0 else 1
        h_sh = m_size if H % max(m_size, 1) == 0 else 1
    budget = 256e6 / 4.0  # f32 elements
    per_row = max((B // b_sh) * (H // h_sh), 1)
    blk = 2048
    while blk > 128 and blk * blk * per_row > budget:
        blk //= 2
    while S % blk != 0 and blk > 1:
        blk //= 2
    return max(blk, 1)


def blockwise_causal_attention(q, k, v, *, q_block: int = 0,
                               kv_block: int = 0):
    """Memory-O(block) causal attention: static unroll over q rows, inner
    scan over that row's kv blocks (flash-style online softmax, pure XLA).

    q: [B, S, H, hd]; k, v: [B, S, KVH, hd]. Exact-FLOP causal: a row's
    inner scan covers exactly the j <= i blocks. All block slicing is done
    by static slices / scan-xs machinery — no dynamic_slice with
    data-derived indices, which GSPMD would handle by replicating the
    operand across the mesh.
    """
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    blk = _attn_block_size(B, S, H, hd)
    nb = S // blk
    scale = hd ** -0.5
    qr = q.reshape(B, S, KVH, G, hd)
    k_blocks = k.reshape(B, nb, blk, KVH, hd).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(B, nb, blk, KVH, hd).transpose(1, 0, 2, 3, 4)
    kpos_blocks = jnp.arange(S, dtype=jnp.int32).reshape(nb, blk)
    pos_in = jnp.arange(blk)

    def make_step(i):
        qi = qr[:, i * blk:(i + 1) * blk]
        qpos = i * blk + pos_in

        def step(carry, xs):
            ob, mb, lb = carry
            kj, vj, kpos = xs
            s = jnp.einsum("bqkgh,bskh->bqkgs", qi, kj,
                           preferred_element_type=f32) * scale
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, _NEG)
            m_new = jnp.maximum(mb, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(mb - m_new)
            l_new = lb * alpha + jnp.sum(p, axis=-1)
            o_new = ob * alpha[..., None] + jnp.einsum(
                "bqkgs,bskh->bqkgh", p.astype(q.dtype), vj,
                preferred_element_type=f32)
            return (o_new, m_new, l_new), None
        return step

    outs = []
    for i in range(nb):
        carry0 = (jnp.zeros((B, blk, KVH, G, hd), f32),
                  jnp.full((B, blk, KVH, G), _NEG, f32),
                  jnp.zeros((B, blk, KVH, G), f32))
        # checkpoint the block step: backward recomputes scores/probs from
        # (q, k, v) instead of stacking f32 probability residuals — without
        # this the saved matrices alone exceed v5e HBM.
        (o, _, l), _ = lax.scan(
            jax.checkpoint(make_step(i)), carry0,
            (k_blocks[:i + 1], v_blocks[:i + 1], kpos_blocks[:i + 1]))
        outs.append(o / jnp.maximum(l[..., None], 1e-30))
    out = jnp.concatenate(outs, axis=1).reshape(B, S, KVH, G, hd)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def ring_attention(q, k, v):
    """Context-parallel causal attention: q/k/v arrive SEQ-SHARDED over the
    'model' axis; kv blocks rotate around the ring with collective-permute
    while each rank accumulates its q rows online (Ring Attention).

    Used when an arch's head count does not divide the model axis (arctic's
    56, whisper's 20, internvl's 14): head-replication would multiply
    per-device attention FLOPs by the axis size AND force an all-gather of
    the hidden states per layer; the ring keeps compute exact-per-rank and
    its only collective is the kv rotation (S*KVH*hd bytes per layer).

    q: [B, S, H, hd]; k, v: [B, S, KVH, hd] (global shapes).
    """
    from repro.sharding import active_rules
    rules = active_rules()
    mesh = rules.mesh
    Pm = mesh.shape["model"]
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    S_loc = S // Pm
    scale = hd ** -0.5
    perm = [(i, (i + 1) % Pm) for i in range(Pm)]

    def block(q_loc, k_loc, v_loc):
        r = lax.axis_index("model")
        Bl = q_loc.shape[0]  # local batch
        qr = q_loc.reshape(Bl, S_loc, KVH, G, hd)
        qpos = r * S_loc + jnp.arange(S_loc)
        o0 = jnp.zeros((Bl, S_loc, KVH, G, hd), f32)
        m0 = jnp.full((Bl, S_loc, KVH, G), _NEG, f32)
        l0 = jnp.zeros((Bl, S_loc, KVH, G), f32)

        def step(carry, j):
            o, m, l, kc, vc = carry
            src = (r - j) % Pm
            kpos = src * S_loc + jnp.arange(S_loc)
            s = jnp.einsum("bqkgh,bskh->bqkgs", qr, kc,
                           preferred_element_type=f32) * scale
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, _NEG)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m2[..., None])
            alpha = jnp.exp(m - m2)
            l2 = l * alpha + jnp.sum(p, axis=-1)
            o2 = o * alpha[..., None] + jnp.einsum(
                "bqkgs,bskh->bqkgh", p.astype(q_loc.dtype), vc,
                preferred_element_type=f32)
            kc = lax.ppermute(kc, "model", perm)
            vc = lax.ppermute(vc, "model", perm)
            return (o2, m2, l2, kc, vc), None

        (o, _, l, _, _), _ = lax.scan(
            jax.checkpoint(step), (o0, m0, l0, k_loc, v_loc),
            jnp.arange(Pm))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(Bl, S_loc, H, hd).astype(q_loc.dtype)

    spec_q = jax.sharding.PartitionSpec(data_axes, "model", None, None)
    fn = jax.shard_map(block, mesh=mesh,
                       in_specs=(spec_q, spec_q, spec_q),
                       out_specs=spec_q, check_vma=False)
    return fn(q, k, v)


def use_ring_attention(cfg, B: int, S: int) -> bool:
    """Ring path: active mesh, heads do NOT divide the model axis (so the
    head-sharded path would replicate), and batch/seq divide the mesh."""
    from repro.sharding import active_rules
    rules = active_rules()
    if rules is None or "model" not in rules.mesh.shape:
        return False
    msize = rules.mesh.shape["model"]
    if msize <= 1 or cfg.n_heads % msize == 0:
        return False
    n_data = rules.mesh.size // msize
    return S % msize == 0 and B % n_data == 0


def full_attention(q, k, v, causal: bool):
    """Plain attention (short kv: whisper encoder/cross-attn)."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qr = q.reshape(B, Sq, KVH, G, hd)
    s = jnp.einsum("bqkgh,bskh->bqkgs", qr, k,
                   preferred_element_type=f32) * hd ** -0.5
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(k.shape[1])[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgs,bskh->bqkgh", p.astype(q.dtype), v,
                   preferred_element_type=f32)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos):
    """Single-token attention against a fixed-size cache.

    q: [B, H, hd]; caches: [B, S, KVH, hd]; pos: [] int32 (tokens < pos+1
    are valid — the current token was already written at ``pos``).
    """
    B, S, KVH, hd = k_cache.shape
    H = q.shape[1]
    G = H // KVH
    qr = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qr, k_cache,
                   preferred_element_type=f32) * hd ** -0.5
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(q.dtype), v_cache,
                   preferred_element_type=f32)
    return o.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention projections (+tables)
# ---------------------------------------------------------------------------


def attn_table(cfg, prefix, L) -> ParamTable:
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()
    s = 0.02
    return {
        prefix + "/wq": ((L, d, H, hd), ("layers", "fsdp", "heads", "head_dim"), ("normal", s)),
        prefix + "/wk": ((L, d, KVH, hd), ("layers", "fsdp", "kv_heads", "head_dim"), ("normal", s)),
        prefix + "/wv": ((L, d, KVH, hd), ("layers", "fsdp", "kv_heads", "head_dim"), ("normal", s)),
        prefix + "/wo": ((L, H, hd, d), ("layers", "heads", "head_dim", "fsdp"), ("normal", s)),
    }


def qkv_proj(cfg, p, x, positions=None, sp: bool = False):
    """x: [B, S, D] -> q [B,S,H,hd], k,v [B,S,KVH,hd] (+RoPE if positions).

    sp=True (ring-attention path): projections run on the seq-sharded
    residual and stay seq-sharded — no gather at all."""
    # dot outputs stay in the activation dtype: their cross-device psums
    # (fsdp-sharded contraction) then move bf16, not f32 (see §Perf)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"].astype(x.dtype))
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    seq_ax = "seq_sp" if sp else "seq"
    q = tag(q, "batch", seq_ax, "heads", None)
    k = tag(k, "batch", seq_ax, "kv_heads", None)
    v = tag(v, "batch", seq_ax, "kv_heads", None)
    return q, k, v


def out_proj(p, o):
    # output dtype == activation dtype so the TP reduce runs in bf16
    return jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_table(cfg, prefix, L, d_ff=None) -> ParamTable:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    s = 0.02
    gated = cfg.mlp_variant in ("swiglu", "geglu")
    t = {
        prefix + "/w_up": ((L, d, ff), ("layers", "fsdp", "ffn"), ("normal", s)),
        prefix + "/w_down": ((L, ff, d), ("layers", "ffn", "fsdp"), ("normal", s)),
    }
    if gated:
        t[prefix + "/w_gate"] = ((L, d, ff), ("layers", "fsdp", "ffn"), ("normal", s))
    return t


def mlp(cfg, p, x):
    # bf16 dot outputs: the up-proj psum (fsdp contraction) and the
    # down-proj TP reduce both move half the bytes vs f32 (see §Perf)
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    if cfg.mlp_variant == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g.astype(f32)).astype(x.dtype) * up
    elif cfg.mlp_variant == "geglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.gelu(g.astype(f32), approximate=True).astype(x.dtype) * up
    elif cfg.mlp_variant == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:  # gelu
        h = jax.nn.gelu(up.astype(f32), approximate=True).astype(x.dtype)
    h = tag(h.astype(x.dtype), "batch", "seq", "ffn")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding / LM head / loss
# ---------------------------------------------------------------------------


def padded_vocab(V: int) -> int:
    """Pad the vocab to a 128 multiple (MXU lane + mesh divisibility):
    odd-sized tables (internvl 151655, whisper 51866) otherwise fall back
    to replicated vocab sharding — Megatron-style padding is standard."""
    return -(-V // 128) * 128


def embed_table(cfg) -> ParamTable:
    V, d = padded_vocab(cfg.vocab_size), cfg.d_model
    t = {"embed": ((V, d), ("vocab", "dmodel"), ("normal", 0.02))}
    if not cfg.tie_embeddings:
        t["unembed"] = ((d, V), ("fsdp", "vocab"), ("normal", 0.02))
    return t


def embed(cfg, params, tokens):
    e = params["embed"].astype(cfg_dtype(cfg))[tokens]
    return tag(e, "batch", "seq", None)


def logits_fn(cfg, params, x):
    """Logits over the REAL vocab (padded columns sliced off; only used on
    last-position decode/prefill outputs, so the slice is tiny)."""
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype),
                        preferred_element_type=f32)
    logits = tag(logits, "batch", "seq", "vocab")
    return logits[..., :cfg.vocab_size]


def softmax_xent(logits, labels, mask=None):
    """Sharded-vocab-safe cross-entropy: no gather over the vocab dim.

    logits: [B, S, V] f32; labels: [B, S] int32; mask: [B, S] (1 = count).
    """
    lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - lmax
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + lmax[..., 0]
    V = logits.shape[-1]
    onehot_sel = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == labels[..., None],
        shifted, 0.0)
    label_logit = jnp.sum(onehot_sel, axis=-1) + lmax[..., 0]
    nll = lse - label_logit
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(f32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(cfg, params, x, labels, mask=None, chunk=512):
    """LM cross-entropy without materializing [B, S, V] logits.

    Scans over sequence chunks; each chunk computes its logits, its masked
    NLL sum and token count, then frees the logits. With the scan's built-in
    rematerialization the backward pass also never holds more than one
    chunk of logits. This is the memory-term optimization that makes the
    256k-vocab archs fit (see EXPERIMENTS.md §Perf).
    """
    B, S, D = x.shape
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S  # fallback: single chunk
    n = S // chunk
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    w = w.astype(x.dtype)
    xs = x.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    ms = None if mask is None else mask.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        tot, cnt = carry
        if ms is None:
            xc, lc = inp
            mc = jnp.ones(lc.shape, f32)
        else:
            xc, lc, mc = inp
        logits = jnp.einsum("bsd,dv->bsv", xc, w, preferred_element_type=f32)
        logits = tag(logits, "batch", "seq", "vocab")
        if logits.shape[-1] != cfg.vocab_size:  # mask padded vocab columns
            pad_mask = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                        >= cfg.vocab_size)
            logits = jnp.where(pad_mask, -1e30, logits)
        lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        shifted = logits - lmax
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + lmax[..., 0]
        sel = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == lc[..., None],
            shifted, 0.0)
        nll = lse - (jnp.sum(sel, axis=-1) + lmax[..., 0])
        mc = mc.astype(f32)
        return (tot + jnp.sum(nll * mc), cnt + jnp.sum(mc)), None

    inps = (xs, ls) if ms is None else (xs, ls, ms)
    # checkpoint: backward recomputes each chunk's logits instead of
    # stacking [n_chunks, B, chunk, V] f32 residuals
    (tot, cnt), _ = lax.scan(jax.checkpoint(body),
                             (jnp.zeros((), f32), jnp.zeros((), f32)), inps)
    return tot / jnp.maximum(cnt, 1.0)


def cfg_dtype(cfg):
    return jnp.dtype(cfg.activation_dtype)


def param_dtype(cfg):
    return jnp.dtype(cfg.param_dtype)
