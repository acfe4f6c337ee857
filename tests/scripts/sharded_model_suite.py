"""Sharded model equivalence on an 8-device (4 data x 2 model) mesh:
train loss, prefill, decode for one arch per family + MoE mode checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, reduced
from repro.dist.sharding import Rules, sanitize_specs
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.models import (decode_step, init_params, param_specs,
                          prefill_step, train_loss)
from repro.models.moe import moe_apply, moe_init

mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)

for name in ["llama3.2-1b", "xlstm-350m", "recurrentgemma-9b",
             "whisper-large-v3", "granite-20b"]:
    cfg = reduced(get_arch(name))
    params = init_params(key, cfg)
    B, S = 8, 32
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    l_ref = float(train_loss(params, batch, cfg, None))
    lo_ref, cache_ref = prefill_step(
        params, {k: v for k, v in batch.items() if k != "labels"}, cfg, None,
        seq_len=S + 4)
    tok = jnp.argmax(lo_ref, -1).astype(jnp.int32)
    lo2_ref, _ = decode_step(params, cache_ref, tok, jnp.int32(S), cfg, None)

    rules_t = Rules(mesh, "train")
    rules_d = Rules(mesh, "decode")
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), key)
    specs = sanitize_specs(param_specs(cfg, rules_t), shapes, mesh)
    with set_mesh(mesh):
        pl_ = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P)))
        l_sh = float(jax.jit(lambda p, b: train_loss(p, b, cfg, rules_t))(
            pl_, batch))
        lo, cache = jax.jit(lambda p, b: prefill_step(
            p, b, cfg, Rules(mesh, "prefill"), seq_len=S + 4))(
            pl_, {k: v for k, v in batch.items() if k != "labels"})
        lo2, _ = jax.jit(lambda p, c, t, po: decode_step(
            p, c, t, po, cfg, rules_d))(pl_, cache, tok, jnp.int32(S))
    assert abs(l_ref - l_sh) < 5e-2, (name, l_ref, l_sh)
    e = float(jnp.max(jnp.abs(lo2 - lo2_ref)))
    assert e < 6e-2, (name, e)
    print(name, "ok")

# MoE modes agree with the local oracle when capacity is drop-free
cfgm = reduced(get_arch("llama4-maverick-400b-a17b"), num_experts=8,
               experts_per_token=2, pad_to=2, capacity_factor=16.0)
p = moe_init(key, cfgm, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfgm.d_model),
                      jnp.float32)
y_ref = moe_apply(p, x, cfgm, None)
rules = Rules(mesh, "train")
with set_mesh(mesh):
    for mode in ("replicated", "alltoall"):
        cm = dataclasses.replace(cfgm, ep_mode=mode)
        for ov in (False, True):
            y = jax.jit(lambda pp, xx: moe_apply(pp, xx, cm, rules,
                                                 overlap=ov))(p, x)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"{mode} overlap={ov}")
    yq = jax.jit(lambda pp, xx: moe_apply(
        pp, xx, dataclasses.replace(cfgm, ep_mode="alltoall"), rules,
        overlap=True, quantize=True))(p, x)
    rel = float(jnp.linalg.norm(yq - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.02, rel
print("moe modes ok")
print("ALL OK")
