"""The §Perf schedule knobs must be semantics-preserving: seq-parallel
prefill, SP residuals, loss chunking, and MoE overlap/quantize produce the
same numbers (quantize within int8 tolerance) as the baseline schedule."""
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, reduced
from repro.dist.sharding import Rules, sanitize_specs
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.models import (StepOptions, init_params, param_specs,
                          prefill_step, train_loss)

mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)

for arch in ("recurrentgemma-9b", "llama3.2-1b"):
    cfg = reduced(get_arch(arch), dtype="float32")
    params = init_params(key, cfg)
    B, S = 8, 64
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), key)

    rules_t = Rules(mesh, "train")
    specs = sanitize_specs(param_specs(cfg, rules_t), shapes, mesh)
    with set_mesh(mesh):
        pl_ = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P)))
        base = float(jax.jit(lambda p, b: train_loss(
            p, b, cfg, rules_t, StepOptions()))(pl_, batch))
        spres = float(jax.jit(lambda p, b: train_loss(
            p, b, cfg, rules_t, StepOptions(sp_residuals=True)))(pl_, batch))
        chunk = float(jax.jit(lambda p, b: train_loss(
            p, b, cfg, rules_t, StepOptions(loss_chunk=16)))(pl_, batch))
        np.testing.assert_allclose(base, spres, rtol=1e-4, err_msg=arch)
        np.testing.assert_allclose(base, chunk, rtol=1e-4, err_msg=arch)

        rules_p = Rules(mesh, "prefill")
        pb = {"tokens": batch["tokens"]}
        lo0, _ = jax.jit(lambda p, b: prefill_step(
            p, b, cfg, rules_p, seq_len=S, opts=StepOptions()))(pl_, pb)
        lo1, _ = jax.jit(lambda p, b: prefill_step(
            p, b, cfg, rules_p, seq_len=S,
            opts=StepOptions(seq_parallel=True)))(pl_, pb)
        np.testing.assert_allclose(np.asarray(lo0), np.asarray(lo1),
                                   atol=5e-3, rtol=5e-3, err_msg=arch)
    print(arch, "ok")
print("ALL OK")
