"""On-chip smoke run of the MTSL main path at mamba2-130m's published widths.

    python chip_smoke.py               # one TPU chip: train, serve, kernels
    python chip_smoke.py --chips 4     # four chips: sharded round vs dense

One process drives every phase; it starts no child that touches JAX. Data
and weights are generated from --seed, nothing is downloaded. The script
refuses to run (nonzero exit, no result line) unless JAX's first device is
a TPU, and any failed check or exception fails the run. Phases:

  train    repro.launch.train.main on the full mamba2-130m config (24
           layers, d_model 768, V=50,280, 4 layers per client tower) with 8
           clients, 4 sequences of 512 tokens each per round, AdamW. Checks
           that every round's loss is finite and that it falls; then times
           warm rounds of the same round program with block_until_ready.
  serve    a full-width ContinuousEngine for 8 clients answers requests of
           mixed prompt lengths. Checks every request gets new_tokens
           tokens in [0, V) and that decode compiled once.
  kernels  ssd_scan (forward and backward), flash_decode and
           flash_attention compiled for the chip at mamba2-130m / GQA
           widths, each against its float32 reference.
  sharded  (--chips 4 only) one MTSL round sharded data=4 through
           shard_round_fn + place_algorithm_state against the dense
           jit_round_fn round on one device of the same host, both with
           float32 activations at "highest" matmul precision.

The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Timings printed here are smoke timings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "mamba2-130m"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every shape the phases use. FULL is the chip run; tests run the same
    phases on the CPU at TINY."""

    smoke: bool = False  # the arch's reduced config instead of its own
    clients: int = 8  # 16 (the config's default) do not fit one v5e chip
    batch_per_client: int = 4
    seq_len: int = 512
    steps: int = 24
    lr: float = 1e-3
    timed_rounds: int = 3
    prompt_lens: tuple = (7, 64, 130, 257, 300, 511, 1, 96, 200, 33, 450, 128)
    new_tokens: int = 16
    serve_chunk: int = 64
    # ssd_scan: B, L, H, P, N, chunk (mamba2-130m: 24 heads of 64, N=128)
    ssd: tuple = (4, 512, 24, 64, 128, 128)
    # flash_decode: B, cap, Hq, Hkv, D, block_k
    decode: tuple = (8, 4096, 32, 8, 128, 128)
    # flash_attention: B, S, Hq, Hkv, D, block
    attention: tuple = (1, 4096, 32, 8, 128, 128)


FULL = Sizes()
TINY = Sizes(smoke=True, clients=2, batch_per_client=2, seq_len=32, steps=4,
             lr=1e-2, timed_rounds=1, prompt_lens=(3, 17, 9, 1, 30),
             new_tokens=4, serve_chunk=8, ssd=(1, 64, 2, 16, 32, 32),
             decode=(2, 64, 4, 2, 32, 32), attention=(1, 64, 4, 2, 32, 32))


def log(msg: str):
    print(msg, flush=True)


def check(ok: bool, what: str):
    log(f"  {'PASS' if ok else 'FAIL'}  {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def _config(sz: Sizes):
    from repro.configs import get_config

    return get_config(ARCH, smoke=sz.smoke).with_updates(
        num_clients=sz.clients)


def phase_train(sz: Sizes, seed: int):
    import jax
    import numpy as np

    from repro.core import lr_policy
    from repro.core.algorithms import HParams, get_algorithm, jit_round_fn
    from repro.core.schedule import full_schedule
    from repro.data.lm import MultiTaskLMSource
    from repro.data.pipeline import client_batches
    from repro.launch import train as launcher
    from repro.models.registry import build_model
    from repro.optim import adamw

    cfg = _config(sz)
    M = sz.clients
    log(f"[train] {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"V={cfg.vocab_size} tower_layers={cfg.split_layers} M={M} "
        f"batch/client={sz.batch_per_client} seq={sz.seq_len} adamw "
        f"lr={sz.lr} rounds={sz.steps}")
    argv = ["--arch", ARCH, "--algorithm", "mtsl", "--num-clients", str(M),
            "--batch-per-client", str(sz.batch_per_client),
            "--seq-len", str(sz.seq_len), "--optimizer", "adamw",
            "--lr", str(sz.lr), "--steps", str(sz.steps), "--log-every", "1",
            "--seed", str(seed)] + (["--smoke"] if sz.smoke else [])
    t0 = time.perf_counter()
    state, history = launcher.main(argv)
    log(f"  launcher run: {time.perf_counter() - t0:.1f} s for {sz.steps} "
        "rounds, compilation included")
    losses = [h["loss"] for h in history]
    check(len(losses) == sz.steps,
          f"{len(losses)} rounds in history, {sz.steps} asked")
    check(all(math.isfinite(x) for x in losses),
          "every round's loss is finite: "
          + " ".join(f"{x:.4f}" for x in losses))
    # the loss is the sum of the M clients' mean token losses; one round's
    # is noisy (2,048 tokens per client), so compare the means of the
    # first and last quarter of the run
    w = max(1, sz.steps // 4)
    first, last = float(np.mean(losses[:w])), float(np.mean(losses[-w:]))
    check(last < first,
          f"loss falls: mean of rounds 1-{w} {first:.4f} -> mean of the "
          f"last {w} {last:.4f} (per client {first / M:.4f} -> "
          f"{last / M:.4f}; ln V = {math.log(cfg.vocab_size):.4f})")

    # warm rounds of the launcher's round program on its final state
    model = build_model(cfg)
    alg = get_algorithm("mtsl")
    hp = HParams(lr=sz.lr, optimizer=adamw(sz.lr),
                 component_lr=lr_policy.server_scaled(M, None))
    round_fn = jit_round_fn(alg, model, M, hp)
    src = MultiTaskLMSource(vocab_size=cfg.vocab_size, num_clients=M,
                            beta=1.0, seed=seed)
    batches = [jax.device_put(b) for b in client_batches(
        src, sz.batch_per_client, steps=sz.timed_rounds + 1,
        seq_len=sz.seq_len, seed=seed + 1, as_numpy=True)]
    sched = full_schedule(M, 1)
    t0 = time.perf_counter()
    state, metrics = round_fn(state, batches[0], sched)
    jax.block_until_ready((state, metrics))
    log(f"  warm-up round (compile or cache hit): "
        f"{time.perf_counter() - t0:.2f} s")
    times = []
    for b in batches[1:]:
        t0 = time.perf_counter()
        state, metrics = round_fn(state, b, sched)
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
    tokens = M * sz.batch_per_client * sz.seq_len
    med = float(np.median(times))
    log("  smoke timing, warm round wall time (block_until_ready): "
        + " ".join(f"{t * 1e3:.1f}" for t in times)
        + f" ms; median {med * 1e3:.1f} ms = {tokens / med:.0f} tokens/s")
    check(math.isfinite(float(metrics["loss"])),
          f"timed rounds' loss is finite: {float(metrics['loss']):.4f}")


def phase_serve(sz: Sizes, seed: int):
    import jax
    import numpy as np

    from repro.core.split import stack_towers
    from repro.models.registry import build_model
    from repro.serve.continuous import ContinuousEngine, Request
    from repro.utils.sharding import strip

    cfg = _config(sz)
    M, V = sz.clients, cfg.vocab_size
    model = build_model(cfg)
    rng = jax.random.PRNGKey(seed)
    params = strip({
        "towers": stack_towers(model.init_tower, rng, M),
        "server": model.init_server(jax.random.fold_in(rng, 1)),
    })
    max_len = max(sz.prompt_lens) + sz.new_tokens
    eng = ContinuousEngine(model, params, M, max_len, slots=M,
                           chunk=sz.serve_chunk,
                           rng=jax.random.fold_in(rng, 2))
    log(f"[serve] ContinuousEngine {ARCH} M={M} slots={M} cap={eng.cap} "
        f"chunk={sz.serve_chunk} requests={len(sz.prompt_lens)} prompt "
        f"lengths {list(sz.prompt_lens)} new_tokens={sz.new_tokens}")
    data = np.random.default_rng(seed)
    prompts = [data.integers(0, V, size=n) for n in sz.prompt_lens]

    def wave(base):
        for i, p in enumerate(prompts):
            eng.submit(Request(id=base + i, client=i % M, tokens=p,
                               new_tokens=sz.new_tokens,
                               temperature=0.0 if i % 2 else 0.8))
        t0 = time.perf_counter()
        out = eng.run()
        return out, time.perf_counter() - t0

    for label, base in (("first wave (compiles)", 0), ("warm wave", 1000)):
        out, dt = wave(base)
        n = len(prompts)
        check(sorted(out) == list(range(base, base + n)),
              f"{label}: all {n} requests answered")
        check(all(len(t) == sz.new_tokens for t in out.values()),
              f"{label}: every request got {sz.new_tokens} tokens")
        check(all(0 <= t.min() and t.max() < V for t in out.values()),
              f"{label}: every token in [0, {V})")
        log(f"  smoke timing, {label}: {dt:.2f} s for "
            f"{n * sz.new_tokens} generated tokens")
    check(eng._decode_step._cache_size() == 1,
          "the decode program compiled once")
    check(eng._extend_step._cache_size() == 1,
          "the prefill-chunk program compiled once")


def _rel_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


# Kernel tolerances, relative to the reference's largest magnitude. The
# kernels take bfloat16 inputs and write bfloat16 outputs (rounding 2**-9
# relative), and accumulate in float32 against a float32 reference at
# "highest" matmul precision on the same bf16-rounded inputs.
KERNEL_TOL = 1e-2


def phase_kernels(sz: Sizes, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import mha_reference
    from repro.kernels.flash_decode.kernel import flash_decode_fwd
    from repro.kernels.platform import interpret_default
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_reference

    interpret = interpret_default()
    rng = np.random.default_rng(seed)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape):
        return jnp.asarray(rng.normal(size=shape), bf16)

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*[jnp.asarray(a, f32) for a in args])

    log(f"[kernels] interpret={interpret} tolerance {KERNEL_TOL} "
        "(max abs error / max abs reference)")

    B, L, H, P, N, chunk = sz.ssd
    x, Bm, Cm = normal((B, L, H, P)), normal((B, L, N)), normal((B, L, N))
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, L, H)), f32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, size=(H,)), f32)
    gy = normal((B, L, H, P))

    def with_vjp(fn):
        def run(*a):
            (y, st), vjp = jax.vjp(fn, *a)
            return y, st, vjp((gy.astype(y.dtype), jnp.zeros_like(st)))
        return run

    y, st, g = jax.jit(with_vjp(lambda *a: ssd_scan(*a, chunk)))(
        x, dt, A, Bm, Cm)
    yr, sr, gr = reference(with_vjp(lambda *a: ssd_reference(
        *a, chunk=chunk)), x, dt, A, Bm, Cm)
    errs = [_rel_err(a, b) for a, b in zip((y, st) + tuple(g),
                                            (yr, sr) + tuple(gr))]
    check(max(errs) <= KERNEL_TOL,
          f"ssd_scan B={B} L={L} H={H} P={P} N={N} chunk={chunk}: "
          + ", ".join(f"{n} {e:.2e}" for n, e in zip(
              ["y", "final state", "dx", "ddt", "dA", "dB", "dC"], errs)))

    B, cap, Hq, Hkv, D, bk = sz.decode
    q, k, v = normal((B, Hkv, Hq // Hkv, D)), normal((B, Hkv, cap, D)), \
        normal((B, Hkv, cap, D))
    kv_valid = jnp.asarray(
        list(rng.integers(1, cap + 1, size=B - 1)) + [cap], jnp.int32)
    out = jax.jit(lambda *a: flash_decode_fwd(
        *a, block_k=bk, interpret=interpret))(q, k, v, kv_valid, kv_valid - 1)

    def decode_ref(q, k, v):
        o = mha_reference(q.reshape(B, 1, Hq, D), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True,
                          q_offset=kv_valid - 1, kv_valid=kv_valid)
        return o.reshape(B, Hkv, Hq // Hkv, D)

    e = _rel_err(out, reference(decode_ref, q, k, v))
    check(e <= KERNEL_TOL,
          f"flash_decode B={B} cap={cap} Hq={Hq} Hkv={Hkv} D={D} "
          f"ragged kv_valid: {e:.2e}")

    B, S, Hq, Hkv, D, blk = sz.attention
    q, k, v = normal((B, Hq, S, D)), normal((B, Hkv, S, D)), \
        normal((B, Hkv, S, D))
    out = jax.jit(lambda *a: flash_attention_fwd(
        *a, causal=True, block_q=blk, block_k=blk, interpret=interpret))(
            q, k, v)

    def attn_ref(q, k, v):
        o = mha_reference(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True)
        return o.transpose(0, 2, 1, 3)

    e = _rel_err(out, reference(attn_ref, q, k, v))
    check(e <= KERNEL_TOL,
          f"flash_attention causal S={S} Hq={Hq} Hkv={Hkv} D={D}: {e:.2e}")


# Sharded-vs-dense tolerance for one round, for the loss (relative) and for
# the largest state difference (relative to the round's largest update).
# The two programs differ only in reduction order and fusion. With the
# config's bfloat16 activations that alone flips bf16 roundings, which 24
# layers of backward amplify to ~10% of the update on the chip, so the
# comparison runs both programs with float32 activations at "highest"
# matmul precision: the sharding, not the rounding, is what is compared.
# A misplaced or mixed-up client shard moves the state by the order of the
# update itself; one bf16 ulp (2**-8) is the bound.
PARITY_TOL = 2.0 ** -8


def phase_sharded(sz: Sizes, seed: int, n_dev: int = 4):
    import jax
    import numpy as np

    from repro.core import lr_policy
    from repro.core.algorithms import (HParams, get_algorithm, jit_round_fn,
                                       place_algorithm_state, shard_round_fn)
    from repro.core.schedule import full_schedule
    from repro.data.lm import MultiTaskLMSource
    from repro.data.pipeline import client_batches
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models.registry import build_model
    from repro.optim import sgd
    from repro.utils.sharding import client_sharding

    cfg = _config(sz).with_updates(dtype="float32")
    M = sz.clients
    model = build_model(cfg)
    alg = get_algorithm("mtsl")
    lr = 0.05
    hp = HParams(lr=lr, optimizer=sgd(lr),
                 component_lr=lr_policy.server_scaled(M, None))
    src = MultiTaskLMSource(vocab_size=cfg.vocab_size, num_clients=M,
                            beta=1.0, seed=seed)
    batch = next(iter(client_batches(src, sz.batch_per_client, steps=1,
                                     seq_len=sz.seq_len, seed=seed,
                                     as_numpy=True)))
    sched = full_schedule(M, 1)
    log(f"[sharded] one mtsl round, {ARCH} M={M} "
        f"batch/client={sz.batch_per_client} seq={sz.seq_len} sgd lr={lr}, "
        f"float32 activations, highest matmul precision: mesh data={n_dev} "
        f"vs dense on {jax.devices()[0]}")

    init = jax.device_get(alg.init_state(model, jax.random.PRNGKey(seed), M,
                                         hp))
    mesh = make_mesh_from_spec(f"data={n_dev}")
    marks = jax.tree.leaves(alg.client_axes(init))

    def spread(tree):
        return [len(x.sharding.device_set)
                for x, m in zip(jax.tree.leaves(tree), marks) if m]

    with jax.default_matmul_precision("highest"):
        dense = jit_round_fn(alg, model, M, hp)
        s_d, m_d = dense(jax.device_put(init), jax.device_put(batch), sched)
        s_d = jax.device_get(s_d)
        loss_d = float(m_d["loss"])
        del dense, m_d

        s0 = place_algorithm_state(alg, init, mesh)
        check(set(spread(s0)) == {n_dev},
              f"placed client leaves span {n_dev} devices "
              f"({len(spread(s0))} leaves)")
        sharded = shard_round_fn(alg, model, M, hp, mesh=mesh)
        s_s, m_s = sharded(s0, jax.device_put(batch, client_sharding(mesh)),
                           sched)
    check(set(spread(s_s)) == {n_dev},
          f"round output client leaves span {n_dev} devices")
    loss_s = float(m_s["loss"])
    s_s = jax.device_get(s_s)

    err = upd = 0.0
    for a, b, i in zip(jax.tree.leaves(s_d), jax.tree.leaves(s_s),
                       jax.tree.leaves(init)):
        if not np.issubdtype(np.asarray(a).dtype, np.floating):
            check(np.array_equal(a, b), f"integer leaf {a.shape} equal")
            continue
        a, b, i = (np.asarray(t, np.float64) for t in (a, b, i))
        err = max(err, float(np.max(np.abs(a - b), initial=0.0)))
        upd = max(upd, float(np.max(np.abs(a - i), initial=0.0)))
    d_loss = abs(loss_d - loss_s) / abs(loss_d)
    check(math.isfinite(loss_d) and math.isfinite(loss_s),
          f"losses finite: dense {loss_d:.6f}, sharded {loss_s:.6f}")
    check(d_loss <= PARITY_TOL,
          f"loss relative difference {d_loss:.2e} <= {PARITY_TOL:.2e}")
    check(err <= PARITY_TOL * upd,
          f"state max difference {err:.3e} = {err / upd:.2e} x the round's "
          f"largest update {upd:.3e} (bound {PARITY_TOL:.2e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve and kernel phases on one chip; "
                         "4: only the sharded-vs-dense round on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro.utils.jit_cache import enable_compilation_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compilation_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(FULL, args.seed)
    else:
        phase_train(FULL, args.seed)
        phase_serve(FULL, args.seed)
        phase_kernels(FULL, args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
