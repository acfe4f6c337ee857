"""Model assembly: init / param specs / train loss / prefill / decode.

Layers are stacked over *repeat units* (the lcm of the block pattern and the
MoE interleave) and applied with ``jax.lax.scan`` so the lowered HLO stays
compact for deep models. Step-level schedule knobs (remat, MoE overlap,
flash block sizes …) live in ``StepOptions`` — the surface the CUCo search
(repro.core) optimizes.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_norm, dense_init, norm_init
from repro.models.moe import moe_param_specs
from repro.models.rglru import (rglru_apply, rglru_init, rglru_init_state,
                                rglru_state_shape)
from repro.models.transformer import (attn_block_apply, attn_block_init,
                                      cache_size)
from repro.models.xlstm import (mlstm_apply, mlstm_init, mlstm_init_state,
                                mlstm_state_shape, slstm_apply, slstm_init,
                                slstm_init_state, slstm_state_shape)
from jax import shard_map

F32 = jnp.float32
MAX_LEARNED_POS = 32768


@dataclass(frozen=True)
class StepOptions:
    """Schedule knobs searched by the CUCo slow path (repro.core)."""
    remat: bool = True
    moe_overlap: bool = False        # CUCo self/remote split dispatch hiding
    moe_quantize: bool = False       # int8 dispatch (paper's quantize phase)
    moe_backend: str = "xla"         # "pallas": fused dispatch kernel (FLUX)
    kv_block: int = 1024             # lax-flash KV block
    flash_threshold: int = 8192
    scan_layers: bool = True
    loss_chunk: int = 0              # >0: chunked CE loss (seq chunks)
    seq_parallel: bool = False       # prefill: activations sharded over seq
    sp_residuals: bool = False       # train: remat carries sharded over seq


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# =============================================================== param init

def _block_init(key, cfg, slot, dtype):
    kind = cfg.block_kind(slot)
    if kind == "mlstm":
        return mlstm_init(key, cfg, dtype)
    if kind == "slstm":
        return slstm_init(key, cfg, dtype)
    if kind == "rglru":
        ks = jax.random.split(key, 2)
        from repro.models.layers import mlp_init
        return {"rglru": rglru_init(ks[0], cfg, dtype),
                "mlp_norm": norm_init(cfg.d_model, cfg.norm, dtype),
                "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)}
    return attn_block_init(key, cfg, slot, dtype, cross=cfg.is_encoder_decoder)


def init_params(key, cfg):
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    Vp, d = cfg.vocab_padded, cfg.d_model
    params = {"embed": dense_init(keys[0], Vp, d, dtype, scale=0.02).reshape(Vp, d)}
    if cfg.learned_pos:
        params["pos"] = dense_init(keys[1], MAX_LEARNED_POS, d, dtype, scale=0.02)
    unit, R = cfg.repeat_unit, cfg.num_repeats

    def stack_slot(slot):
        # one vmapped init per slot: the same values as R separate inits
        # stacked, in one small program (R unrolled inits take minutes to
        # compile at full width, and stacking them holds two copies)
        ks = jax.random.split(jax.random.fold_in(keys[2], slot), R)
        return jax.vmap(lambda k: _block_init(k, cfg, slot, dtype))(ks)

    params["blocks"] = {f"s{i}": stack_slot(i) for i in range(unit)}
    params["final_norm"] = norm_init(d, cfg.norm, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[3], d, Vp, dtype)
    if cfg.is_encoder_decoder:
        ks = jax.random.split(keys[4], cfg.enc_layers)
        enc_leaves = [attn_block_init(k, cfg, 10**6, dtype, cross=False)
                      for k in ks]                      # 10**6: never MoE
        params["enc"] = {
            "pos": dense_init(keys[5], cfg.enc_seq, d, dtype, scale=0.02),
            "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_leaves),
            "final_norm": norm_init(d, cfg.norm, dtype),
        }
    return params


# ============================================================== param specs

def _attn_specs(cfg, rules, cross):
    sp = {
        "norm": {"w": P(None)} if cfg.norm == "rmsnorm" else {"w": P(None), "b": P(None)},
        "attn": {"q": P(None, rules.axes("heads")),
                 "k": P(None, rules.axes("kv_heads")),
                 "v": P(None, rules.axes("kv_heads")),
                 "o": P(rules.axes("heads"), None)},
        "mlp_norm": {"w": P(None)} if cfg.norm == "rmsnorm" else {"w": P(None), "b": P(None)},
    }
    if cross:
        sp["cross_norm"] = sp["norm"]
        sp["cross"] = sp["attn"]
    return sp


def _norm_spec(cfg):
    return {"w": P(None)} if cfg.norm == "rmsnorm" else {"w": P(None), "b": P(None)}


def _block_specs(cfg, slot, rules):
    kind = cfg.block_kind(slot)
    ff = rules.axes("ff")
    if kind == "mlstm":
        return {"norm": _norm_spec(cfg), "up": P(None, ff), "q": P(None, ff),
                "k": P(None, ff), "v": P(None, ff), "wi": P(None, None),
                "wf": P(None, None), "bf": P(None), "bi": P(None),
                "hnorm": {"w": P(None)}, "down": P(ff, None)}
    if kind == "slstm":
        return {"norm": _norm_spec(cfg), "w": P(None, ff), "r": P(None, None, None),
                "b": P(None), "ffn_norm": _norm_spec(cfg),
                "ff_gate": P(None, ff), "ff_up": P(None, ff), "ff_down": P(ff, None)}
    if kind == "rglru":
        return {"rglru": {"norm": _norm_spec(cfg), "in_a": P(None, ff),
                          "in_b": P(None, ff), "conv_w": P(None, ff),
                          "conv_b": P(ff), "wr": P(None, ff), "wi": P(None, ff),
                          "lam": P(ff), "out": P(ff, None)},
                "mlp_norm": _norm_spec(cfg),
                "mlp": _mlp_specs(cfg, rules)}
    sp = _attn_specs(cfg, rules, cfg.is_encoder_decoder)
    if cfg.layer_is_moe(slot):
        sp["moe"] = moe_param_specs(cfg, rules)
    else:
        sp["mlp"] = _mlp_specs(cfg, rules)
    return sp


def _mlp_specs(cfg, rules):
    ff = rules.axes("ff")
    if cfg.act == "swiglu":
        return {"gate": P(None, ff), "up": P(None, ff), "down": P(ff, None)}
    return {"up": P(None, ff), "down": P(ff, None)}


def _prepend(spec, extra=None):
    """Add the leading stacking dim (repeats) to every leaf spec."""
    return jax.tree.map(lambda s: P(extra, *s), spec,
                        is_leaf=lambda s: isinstance(s, P))


def param_specs(cfg, rules):
    """Pytree of PartitionSpec matching init_params(cfg). Strict-divisible."""
    vocab = rules.axes("vocab")
    specs = {"embed": P(vocab, None)}
    if cfg.learned_pos:
        specs["pos"] = P(None, None)
    specs["blocks"] = {f"s{i}": _prepend(_block_specs(cfg, i, rules))
                       for i in range(cfg.repeat_unit)}
    specs["final_norm"] = _norm_spec(cfg)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, vocab)
    if cfg.is_encoder_decoder:
        specs["enc"] = {
            "pos": P(None, None),
            "blocks": _prepend(_attn_specs(cfg, rules, cross=False)
                               | {"mlp": _mlp_specs(cfg, rules)}),
            "final_norm": _norm_spec(cfg),
        }
    return specs


# ============================================================ embed / logits

def embed_lookup(embed, ids, rules):
    """Vocab-parallel embedding lookup (Megatron-style masked psum)."""
    if rules is None or rules.mesh is None or rules.axes("vocab") is None:
        return embed[ids]
    tp = rules.axes("vocab")
    Vp = embed.shape[0]
    tp_size = rules.size("vocab")
    if Vp % tp_size != 0:
        return embed[ids]
    B = ids.shape[0]
    bspec = rules.axes("batch") if (rules.dp_size() and B % rules.dp_size() == 0
                                    and B >= rules.dp_size()) else None

    def body(emb_l, ids_l):
        Vl = emb_l.shape[0]
        lo = jax.lax.axis_index(tp) * Vl
        loc = ids_l - lo
        ok = (loc >= 0) & (loc < Vl)
        out = emb_l[jnp.clip(loc, 0, Vl - 1)] * ok[..., None].astype(emb_l.dtype)
        return jax.lax.psum(out, tp)

    return shard_map(
        body, mesh=rules.mesh,
        in_specs=(P(tp, None), P(bspec, None)),
        out_specs=P(bspec, None, None), check_vma=False,
    )(embed, ids)


def lm_logits(params, x, cfg, rules):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ w.astype(x.dtype)).astype(F32)
    Vp = logits.shape[-1]
    if Vp > cfg.vocab_size:
        valid = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(valid, logits, -1e30)
    if rules is not None:
        logits = rules.shard(logits, "batch", None, "vocab")
    return logits


# ================================================================== caches

def init_cache(cfg, B, seq_len, dtype=None):
    """Decode cache pytree (concrete zeros). Structure mirrors cache_specs."""
    dtype = dtype or _dtype(cfg)
    unit, R = cfg.repeat_unit, cfg.num_repeats
    Hkv, hd = cfg.num_kv_heads, cfg.hd
    out = {}
    for i in range(unit):
        kind = cfg.block_kind(i)
        if kind == "mlstm":
            st = mlstm_init_state(cfg, B)
            out[f"s{i}"] = jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape).copy(), st)
        elif kind == "slstm":
            st = slstm_init_state(cfg, B)
            out[f"s{i}"] = jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape).copy(), st)
        elif kind == "rglru":
            st = rglru_init_state(cfg, B, dtype)
            out[f"s{i}"] = jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape).copy(), st)
        else:
            Sc = cache_size(cfg, kind, seq_len)
            c = {"k": jnp.zeros((R, B, Sc, Hkv, hd), dtype),
                 "v": jnp.zeros((R, B, Sc, Hkv, hd), dtype),
                 "kpos": jnp.full((R, Sc), -10**9, jnp.int32)}
            if cfg.is_encoder_decoder:
                c["ck"] = jnp.zeros((R, B, cfg.enc_seq, Hkv, hd), dtype)
                c["cv"] = jnp.zeros((R, B, cfg.enc_seq, Hkv, hd), dtype)
            out[f"s{i}"] = c
    return out


def cache_specs(cfg, B, seq_len, rules):
    """ShapeDtypeStruct + PartitionSpec trees for the decode cache."""
    dtype = _dtype(cfg)
    unit, R = cfg.repeat_unit, cfg.num_repeats
    Hkv, hd = cfg.num_kv_heads, cfg.hd
    shapes, specs = {}, {}
    for i in range(unit):
        kind = cfg.block_kind(i)
        if kind in ("mlstm", "slstm", "rglru"):
            sh = (mlstm_state_shape(cfg, B) if kind == "mlstm" else
                  slstm_state_shape(cfg, B) if kind == "slstm" else
                  rglru_state_shape(cfg, B))
            shapes[f"s{i}"] = {k: jax.ShapeDtypeStruct(
                (R,) + v, dtype if (kind == "rglru" and k == "conv") else F32)
                for k, v in sh.items()}
            specs[f"s{i}"] = {k: rules.param_spec((R,) + v, None, "batch",
                                                  *([None] * (len(v) - 1)))
                              for k, v in sh.items()}
        else:
            Sc = cache_size(cfg, kind, seq_len)
            kv_shape = (R, B, Sc, Hkv, hd)
            shapes[f"s{i}"] = {
                "k": jax.ShapeDtypeStruct(kv_shape, dtype),
                "v": jax.ShapeDtypeStruct(kv_shape, dtype),
                "kpos": jax.ShapeDtypeStruct((R, Sc), jnp.int32)}
            kv_spec = rules.param_spec(kv_shape, None, "batch", "seq_kv", None, None)
            specs[f"s{i}"] = {"k": kv_spec, "v": kv_spec, "kpos": P(None, None)}
            if cfg.is_encoder_decoder:
                csh = (R, B, cfg.enc_seq, Hkv, hd)
                shapes[f"s{i}"]["ck"] = jax.ShapeDtypeStruct(csh, dtype)
                shapes[f"s{i}"]["cv"] = jax.ShapeDtypeStruct(csh, dtype)
                cs = rules.param_spec(csh, None, "batch", None, None, None)
                specs[f"s{i}"]["ck"] = cs
                specs[f"s{i}"]["cv"] = cs
    return shapes, specs


# ================================================================ forward

def _apply_block(p, x, cfg, slot, rules, positions, *, causal, cache, pos,
                 enc_out, opts):
    kind = cfg.block_kind(slot)
    if kind == "mlstm":
        return mlstm_apply(p, x, cfg, state=cache, decode=pos is not None)
    if kind == "slstm":
        return slstm_apply(p, x, cfg, state=cache, decode=pos is not None)
    if kind == "rglru":
        x, st = rglru_apply(p["rglru"], x, cfg, state=cache, decode=pos is not None)
        from repro.models.layers import mlp_apply
        xn = apply_norm(p["mlp_norm"], x, cfg.norm)
        x = x + mlp_apply(p["mlp"], xn, cfg.act)
        if rules is not None:
            seq_ax = "seq_act" if (opts and opts.seq_parallel) else None
            x = rules.shard(x, "batch", seq_ax, None)
        return x, st
    return attn_block_apply(p, x, cfg, kind, rules, positions, causal=causal,
                            cache=cache, pos=pos, enc_out=enc_out, opts=opts)


def apply_blocks(params_blocks, x, cfg, rules, positions, *, causal=True,
                 cache=None, pos=None, enc_out=None, opts=None,
                 return_cache=False):
    unit = cfg.repeat_unit
    opts = opts or StepOptions()

    def body(carry, xs):
        h = carry
        slot_params, slot_cache = xs
        new_caches = {}
        for i in range(unit):
            key = f"s{i}"
            c = slot_cache.get(key) if slot_cache else None
            h, nc = _apply_block(slot_params[key], h, cfg, i, rules, positions,
                                 causal=causal, cache=c, pos=pos,
                                 enc_out=enc_out, opts=opts)
            if opts.seq_parallel and rules is not None:
                h = rules.shard(h, "batch", "seq_act", None)
            new_caches[key] = nc
        if opts.sp_residuals and rules is not None:
            # remat saves the scan carry: store it sequence-sharded (SP
            # activation checkpoints — trades an all-gather per layer for
            # a tp-fold smaller residual footprint)
            h = rules.shard(h, "batch", "seq_res", None)
        if not return_cache:
            return h, None
        return h, new_caches

    if opts.remat and pos is None:
        # prevent_cse=False is only safe under scan (XLA would CSE the
        # rematerialized forward away in the unrolled path).
        body = jax.checkpoint(body, prevent_cse=not opts.scan_layers)

    if opts.scan_layers and cfg.num_repeats > 1:
        x, ys = jax.lax.scan(body, x, (params_blocks, cache))
        return x, ys
    # unrolled
    ys = []
    R = cfg.num_repeats
    for r in range(R):
        sl_p = jax.tree.map(lambda a: a[r], params_blocks)
        sl_c = jax.tree.map(lambda a: a[r], cache) if cache is not None else None
        x, y = body(x, (sl_p, sl_c))
        ys.append(y)
    if return_cache and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return x, ys


def encode(params, frames, cfg, rules, opts=None):
    """Whisper encoder over stub frame embeddings (B, enc_seq, d)."""
    x = frames + params["enc"]["pos"][None, :frames.shape[1]].astype(frames.dtype)
    pos = jnp.arange(frames.shape[1])
    opts = opts or StepOptions()

    def body(h, sl):
        h, _ = attn_block_apply(sl, h, cfg, "attn", rules, pos, causal=False,
                                opts=opts)
        return h, None

    if opts.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["enc"]["blocks"])
    return apply_norm(params["enc"]["final_norm"], x, cfg.norm)


def forward(params, batch, cfg, rules, opts=None, return_cache=False,
            cache=None):
    """Training / prefill forward. batch: {"tokens", ["frames"|"patches"]}."""
    opts = opts or StepOptions()
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens, rules).astype(_dtype(cfg))
    if cfg.num_patch_tokens and "patches" in batch:
        Pn = batch["patches"].shape[1]
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x[:, Pn:]], axis=1)
    if cfg.learned_pos:
        x = x + params["pos"][:S][None].astype(x.dtype)
    if rules is not None:
        x = rules.shard(x, "batch", None, None)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, batch["frames"].astype(x.dtype), cfg, rules, opts)
    positions = jnp.arange(S)
    x, new_cache = apply_blocks(params["blocks"], x, cfg, rules, positions,
                                causal=True, cache=cache, enc_out=enc_out,
                                opts=opts, return_cache=return_cache)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, new_cache


def _ce_terms(params, x, labels, cfg, rules):
    logits = lm_logits(params, x, cfg, rules)
    mask = (labels >= 0)
    labels_c = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll), jnp.sum(mask)


def train_loss(params, batch, cfg, rules, opts=None):
    opts = opts or StepOptions()
    x, _ = forward(params, batch, cfg, rules, opts)
    labels = batch["labels"]
    S = labels.shape[1]
    ck = opts.loss_chunk
    if ck and S % ck == 0 and S > ck:
        # chunked CE: never materialize full (B, S, V) logits
        xs = x.reshape(x.shape[0], S // ck, ck, x.shape[-1]).swapaxes(0, 1)
        ls = labels.reshape(labels.shape[0], S // ck, ck).swapaxes(0, 1)

        def step(carry, blk):
            xb, lb = blk
            n, c = _ce_terms(params, xb, lb, cfg, rules)
            return (carry[0] + n, carry[1] + c), None

        step = jax.checkpoint(step, prevent_cse=False)
        (nll, cnt), _ = jax.lax.scan(step, (jnp.zeros((), F32), jnp.zeros((), F32)),
                                     (xs, ls))
        return nll / jnp.maximum(cnt, 1)
    nll, cnt = _ce_terms(params, x, labels, cfg, rules)
    return nll / jnp.maximum(cnt, 1)


def prefill_step(params, batch, cfg, rules, seq_len=None, opts=None):
    """Prefill: build the decode cache + last-position logits."""
    opts = opts or StepOptions()
    S = batch["tokens"].shape[1]
    B = batch["tokens"].shape[0]
    cache = init_cache(cfg, B, seq_len or S)
    x, new_cache = forward(params, batch, cfg, rules, opts, return_cache=True,
                           cache=cache)
    logits = lm_logits(params, x[:, -1:], cfg, rules)
    return logits, new_cache


def decode_step(params, cache, token, pos, cfg, rules, opts=None):
    """One decode step. token: (B, 1) int32; pos: scalar int32."""
    opts = opts or StepOptions()
    x = embed_lookup(params["embed"], token, rules).astype(_dtype(cfg))
    if cfg.learned_pos:
        x = x + jax.lax.dynamic_slice(params["pos"], (pos % MAX_LEARNED_POS, 0),
                                      (1, cfg.d_model))[None].astype(x.dtype)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos
    x, new_cache = apply_blocks(params["blocks"], x, cfg, rules, positions,
                                causal=True, cache=cache, pos=pos, opts=opts,
                                return_cache=True)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, x, cfg, rules)
    return logits, new_cache
