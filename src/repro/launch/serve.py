"""Serving driver: continuous batching through ``Engine.serve``, or
disaggregated prefill/decode tiers.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
        [--requests 4] [--prompt-len 64] [--new-tokens 16] [--reduced] \
        [--disaggregated]

Serves the arch at its full width; ``--reduced`` swaps in the arch's
smoke-size config. Parameters and prompts are drawn from ``--seed``.
Prompt lengths step down from ``--prompt-len`` so that requests differ.
``Engine.serve`` batches attention caches only (no recurrent state, no
encoder frames or patch embeddings): those archs are refused there
(ROADMAP R3). ``--disaggregated`` serves one batch of ``--requests``
prompts of ``--prompt-len`` tokens through the prefill tier's handoff
(``Engine.prefill_remote``) and the decode tier
(``Engine.decode_from_handoff``); it takes every arch.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.configs.base import ATTN_KINDS
from repro.models import init_params
from repro.serve import Engine, Request, Scheduler, ServeConfig


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", "--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's smoke-size config, not its "
                         "full width")
    ap.add_argument("--disaggregated", action="store_true",
                    help="hand the prefill tier's cache to the decode tier "
                         "instead of continuous batching")
    return ap


def model_config(args):
    cfg = get_arch(args.arch)
    return reduced(cfg) if args.reduced else cfg


def check_servable(cfg):
    """Refuse what ``Engine.serve`` cannot batch (ROADMAP R3)."""
    kinds = {cfg.block_kind(i) for i in range(cfg.repeat_unit)}
    if cfg.is_encoder_decoder or cfg.num_patch_tokens or kinds - set(ATTN_KINDS):
        raise ValueError(f"{cfg.name}: Engine.serve batches attention "
                         f"caches of token-only prompts; block kinds "
                         f"{sorted(kinds)}")


def build_engine(cfg, *, seed, max_seq, temperature=0.0):
    """An :class:`Engine` over parameters drawn from ``seed``. The init is
    jitted so that a full-width model is built on the device in one
    program (no per-layer host round trips, no second stacked copy)."""
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return Engine(cfg, params, ServeConfig(max_seq=max_seq,
                                           temperature=temperature,
                                           seed=seed))


def make_requests(cfg, prompt_lens, new_tokens, seed):
    """One request per prompt length, token ids drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(0, cfg.vocab_size, n), new_tokens)
            for rid, n in enumerate(prompt_lens)]


def serve(engine, requests):
    """Submit ``requests`` and run ``Engine.serve`` until all finish.
    Every prompt is admitted in the first step. Returns ``{rid: tokens}``."""
    sched = Scheduler(
        token_budget=sum(r.prompt_len for r in requests) + len(requests),
        max_batch=len(requests), metrics=engine.metrics)
    for r in requests:
        sched.submit(r)
    return engine.serve(sched)


def serve_disaggregated(engine, n, prompt_len, new_tokens, seed):
    """One batch of ``n`` prompts of ``prompt_len`` tokens: the prefill
    tier hands its cache over, the decode tier generates from it.
    Encoder frames and patch embeddings, where the arch takes them, are
    zeros. Returns ``{rid: tokens}``."""
    cfg = engine.cfg
    rng = np.random.default_rng(seed)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (n, prompt_len))
             .astype(np.int32)}
    if cfg.is_encoder_decoder:
        batch["frames"] = np.zeros((n, cfg.enc_seq, cfg.d_model), np.float32)
    if cfg.num_patch_tokens:
        batch["patches"] = np.zeros((n, cfg.num_patch_tokens, cfg.d_model),
                                    np.float32)
    toks = engine.decode_from_handoff(engine.prefill_remote(batch),
                                      new_tokens)
    return {rid: toks[rid] for rid in range(n)}


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = model_config(args)
    if args.disaggregated:
        lens = [args.prompt_len] * args.requests
    else:
        check_servable(cfg)
        lens = [max(1, args.prompt_len - 7 * i) for i in range(args.requests)]
    eng = build_engine(cfg, seed=args.seed,
                       max_seq=max(lens) + args.new_tokens + 1,
                       temperature=args.temperature)
    t0 = time.perf_counter()
    if args.disaggregated:
        out = serve_disaggregated(eng, args.requests, args.prompt_len,
                                  args.new_tokens, args.seed)
    else:
        out = serve(eng, make_requests(cfg, lens, args.new_tokens,
                                       args.seed))
    dt = time.perf_counter() - t0
    total = sum(len(t) for t in out.values())
    mode = "disaggregated" if args.disaggregated else "Engine.serve"
    print(f"[serve] {cfg.name} on {jax.devices()[0].device_kind} ({mode}): "
          f"{len(out)} requests, {total} tokens in {dt:.2f}s "
          f"(compile included)")
    print("[serve] request 0:", np.asarray(out[0][:16]))


if __name__ == "__main__":
    main()
