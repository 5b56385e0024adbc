"""Serving launcher: loads (or random-inits) a split model and serves
batched requests with per-client routing through the MTSL towers.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
        --prompt-len 32 --new-tokens 16
    # quick serving microbenchmark (prefill ms / decode tok/s / tok/s/slot):
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
        --bench --engine continuous
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.split import stack_towers
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine
from repro.train.checkpoint import load_checkpoint
from repro.utils.jit_cache import enable_compilation_cache
from repro.utils.sharding import strip


def _load_serve_params(path: str):
    """{"towers","server"} params from either checkpoint format: an
    Algorithm-registry state (train/loop.py) or a raw {"params": ...} tree
    (examples/train_mtsl_lm.py)."""
    tree = load_checkpoint(path)
    if isinstance(tree, dict) and "algorithm" in tree and "state" in tree:
        from repro.core.algorithms import get_algorithm

        alg = get_algorithm(tree["algorithm"])
        if alg.serve_params is None:
            raise SystemExit(
                f"algorithm {alg.name!r} states are not directly servable "
                "(per-client servers / mixtures have no single split model)")
        return alg.serve_params(alg.state_from_tree(tree["state"]))
    return tree["params"]


def run_bench(model, params, cfg, M: int, b: int, prompt_len: int,
              new_tokens: int, engine_kind: str, chunk: int = 8) -> dict:
    """Timed serving smoke: one warm-up pass (compile), then a measured
    prefill phase and decode phase. Returns prefill_ms / decode_tok_s /
    tok_s_per_slot (slots = M*b rows for both engines)."""
    rng = jax.random.PRNGKey(0)
    max_len = prompt_len + new_tokens
    slots = M * b
    prompts = np.asarray(jax.random.randint(
        rng, (slots, prompt_len), 0, cfg.vocab_size))

    if engine_kind == "continuous":
        from repro.serve.continuous import ContinuousEngine, Request

        chunk = min(chunk, prompt_len)
        eng = ContinuousEngine(model, params, M, max_len,
                               slots=slots, chunk=chunk)

        def submit_all():
            for i in range(slots):
                eng.submit(Request(id=i, client=i % M, tokens=prompts[i],
                                   new_tokens=new_tokens))

        submit_all()  # warm-up: compiles extend + decode
        eng.run()
        submit_all()
        eng.sync()
        t0 = time.time()
        n_chunks = eng.prefill_all()
        eng.sync()
        t1 = time.time()
        emitted = eng.decode_all()
        eng.sync()
        t2 = time.time()
        eng.run()  # drain result buffers
        prefill_s, decode_s = t1 - t0, t2 - t1
        decode_tokens = emitted
        extra = {"extend_chunks": n_chunks,
                 "decode_compiles": eng._decode_step._cache_size()}
    else:
        engine = ServeEngine(model, params, M, max_len)
        inputs = {"tokens": jax.numpy.asarray(
            prompts.reshape(M, b, prompt_len))}
        engine.generate_sequential(inputs, new_tokens)  # warm-up
        t0 = time.time()
        logits, caches = engine._prefill(engine.params, inputs)
        tok = engine._sample(logits, 0.0, None, 0).reshape(M, b, 1)
        jax.block_until_ready(tok)
        t1 = time.time()
        for t in range(new_tokens - 1):
            logits, caches = engine._decode(engine.params, caches, tok,
                                            prompt_len + t)
            tok = engine._sample(logits, 0.0, None, t + 1).reshape(M, b, 1)
        jax.block_until_ready(tok)
        t2 = time.time()
        prefill_s, decode_s = t1 - t0, t2 - t1
        decode_tokens = slots * (new_tokens - 1)
        extra = {}

    decode_tok_s = decode_tokens / max(decode_s, 1e-9)
    return {
        "engine": engine_kind,
        "arch": cfg.name,
        "slots": slots,
        "prefill_ms": prefill_s * 1e3,
        "decode_tok_s": decode_tok_s,
        "tok_s_per_slot": decode_tok_s / slots,
        **extra,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU tests); default is "
                         "the registered config at its published widths")
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--engine", choices=("continuous", "sequential"),
                    default="continuous")
    ap.add_argument("--bench", action="store_true",
                    help="timed prefill/decode smoke instead of generation")
    ap.add_argument("--seed", type=int, default=0,
                    help="base PRNG seed: params init, prompts, and the "
                         "engine's per-request sampling keys")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    M, b = cfg.num_clients, args.batch_per_client
    rng = jax.random.PRNGKey(args.seed)
    if args.checkpoint:
        params = _load_serve_params(args.checkpoint)
    else:
        params = strip({
            "towers": stack_towers(model.init_tower, rng, M),
            "server": model.init_server(jax.random.fold_in(rng, 1)),
        })

    if args.bench:
        metrics = run_bench(model, params, cfg, M, b, args.prompt_len,
                            args.new_tokens, args.engine)
        print(f"[{metrics['engine']}] prefill {metrics['prefill_ms']:.1f} ms | "
              f"decode {metrics['decode_tok_s']:.1f} tok/s | "
              f"{metrics['tok_s_per_slot']:.1f} tok/s/slot "
              f"({metrics['slots']} slots)")
        return metrics

    max_len = args.prompt_len + args.new_tokens
    engine = ServeEngine(model, params, M, max_len, sample_seed=args.seed)
    # distinct fold_in per consumer: reusing one key across draws would
    # correlate the token/vision/audio streams (repro-lint: prng-key-reuse)
    inputs = {"tokens": jax.random.randint(
        jax.random.fold_in(rng, 10), (M, b, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.family == "vlm":
        inputs["vis"] = jax.random.normal(
            jax.random.fold_in(rng, 11), (M, b, cfg.vis_seq, cfg.vis_dim))
    if cfg.family == "encdec":
        inputs["frames"] = jax.random.normal(
            jax.random.fold_in(rng, 12), (M, b, cfg.encoder_seq, cfg.d_model))

    gen = (engine.generate if args.engine == "continuous"
           else engine.generate_sequential)
    t0 = time.time()
    out = gen(inputs, args.new_tokens, temperature=args.temperature,
              rng=jax.random.fold_in(rng, 2))
    dt = time.time() - t0
    total = M * b * args.new_tokens
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. compile)")
    print("sample (client 0):", np.asarray(out[0, 0])[:16])
    return out


if __name__ == "__main__":
    enable_compilation_cache()
    main()
