"""Training launcher.

End-to-end training on synthetic heterogeneous data: --arch names a
registered config, run at its published widths; --smoke swaps in the
reduced variant that runs in seconds on the CPU.

Massive-M scale-out (core/client_axis.py, README "Scaling"):
  * `--mesh data=N[,model=K[,pod=P]]` shards the client axis of every
    round over the device mesh (client leaves over ("pod","data"), the
    rest replicated; federation means become all-reduces). Use
    XLA_FLAGS=--xla_force_host_platform_device_count=N to emulate devices
    on CPU; num-clients must divide by the client-shard count.
  * `--client-chunk C` runs each round's per-client block as a scan over
    chunks of C clients — compile time and peak memory stay flat as the
    client count grows. Composes with --mesh (C must be a multiple of the
    client-shard count). Defaults preserve the single-device trajectory
    bit for bit.

`--algorithm` accepts anything in the Algorithm registry
(core/algorithms.py): mtsl, splitfed, fedavg, fedprox, fedem, smofi,
parallelsfl, plus any algorithm registered by user code before invoking
`main`. Algorithm hyper-parameters are registry-driven: `--hp key=value`
(repeatable) sets any scalar HParams field, so a newly registered
algorithm's knobs get CLI exposure with no launcher change; the historic
per-algorithm flags (--prox-mu, --momentum, --num-clusters) remain as
deprecated aliases.

`--data cached --cache-dir D` swaps per-round host synthesis for
deterministic mmap'd shard reads from a build-once on-disk cache
(data/shards.py; built on first use, or offline via
tools/cache_dataset.py). `--dirichlet-alpha A` builds the cache as a
Dirichlet(A) non-IID partition of a pooled corpus — the standard
heterogeneity protocol. Iteration is resharding-invariant: the same
(seed, round) yields the same round batch for any shard count or mesh.

`--topology` deploys the run on an explicit edge graph (core/topology.py):
star | clustered | hierarchical | multi-server, with per-link physics from
--uplink-mbps/--downlink-mbps/--backbone-mbps/--link-latency-ms. The
training math is unchanged; history gains "sim_time", the simulated
wall-clock (per-client compute + per-link transfer).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
        --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch paper-mlp \
        --algorithm fedem --hp num_components=4
    PYTHONPATH=src python -m repro.launch.train --arch paper-mlp \
        --topology multi-server --num-servers 3 --uplink-mbps 10
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.configs import get_config
from repro.core import lr_policy
from repro.core.algorithms import (
    HParams,
    get_algorithm,
    list_algorithms,
    num_rounds,
)
from repro.core.schedule import ScheduleConfig, padded_batch_per_client
from repro.core.topology import TOPOLOGIES, build_topology, mbps
from repro.data import shards
from repro.data.lm import MultiTaskLMSource
from repro.data.pipeline import client_batches
from repro.data.synthetic import MultiTaskImageSource
from repro.launch.mesh import make_mesh_from_spec, parse_mesh_spec
from repro.models.registry import build_model
from repro.optim import adamw, sgd
from repro.train.loop import TrainConfig, train
from repro.utils.jit_cache import enable_compilation_cache

# scalar HParams fields settable via --hp key=value (registry-driven: any
# new field with a bool/int/float default is exposed automatically)
_HP_FIELDS = {
    f.name: f.default
    for f in dataclasses.fields(HParams)
    if isinstance(f.default, (bool, int, float))
}


def _coerce_hp(key: str, value: str):
    default = _HP_FIELDS[key]
    if isinstance(default, bool):
        if value.lower() in ("1", "true", "yes", "on"):
            return True
        if value.lower() in ("0", "false", "no", "off"):
            return False
        raise argparse.ArgumentTypeError(
            f"--hp {key}= expects a boolean, got {value!r}")
    return type(default)(value)


def parse_hp_overrides(items) -> dict:
    """['key=value', ...] -> validated HParams override dict."""
    out = {}
    for item in items:
        key, sep, value = item.partition("=")
        key = key.strip().replace("-", "_")
        if not sep:
            raise SystemExit(f"--hp expects key=value, got {item!r}")
        if key not in _HP_FIELDS:
            raise SystemExit(
                f"unknown hyper-parameter {key!r}; --hp accepts: "
                f"{', '.join(sorted(_HP_FIELDS))}")
        try:
            out[key] = _coerce_hp(key, value.strip())
        except (ValueError, argparse.ArgumentTypeError) as e:
            raise SystemExit(f"bad --hp {item!r}: {e}") from None
    return out


def _cached_dataset(args, src, M, is_classifier):
    """Open (or build-once) the on-disk client cache for --data cached."""
    if not args.cache_dir:
        raise SystemExit("--data cached requires --cache-dir")
    seq = None if is_classifier else args.seq_len
    try:
        ds = shards.load_cache(args.cache_dir)
    except FileNotFoundError:
        if args.dirichlet_alpha is not None:
            # the standard non-IID protocol: pool an IID corpus, then
            # Dirichlet(alpha)-partition it across the M clients
            corpus = shards.pooled_corpus(src, M * args.cache_examples,
                                          seed=args.seed, seq_len=seq)
            shards.build_dirichlet_cache(args.cache_dir, corpus, M,
                                         args.dirichlet_alpha,
                                         seed=args.seed)
        else:
            shards.build_cache(args.cache_dir, src, args.cache_examples,
                               seq_len=seq, seed=args.seed)
        print(f"built client cache at {args.cache_dir}")
        ds = shards.load_cache(args.cache_dir)
    if ds.num_clients_total != M:
        raise SystemExit(
            f"cache at {args.cache_dir!r} holds {ds.num_clients_total} "
            f"clients but the run needs {M} (rebuild with "
            f"tools/cache_dataset.py or point --cache-dir elsewhere)")
    want_kind = "image" if is_classifier else "lm"
    if ds.kind != want_kind:
        raise SystemExit(
            f"cache at {args.cache_dir!r} is kind {ds.kind!r} but --arch "
            f"needs {want_kind!r}")
    if seq is not None and ds.seq_len is not None and seq > ds.seq_len:
        raise SystemExit(
            f"--seq-len {seq} exceeds the cached sequence length "
            f"{ds.seq_len} at {args.cache_dir!r}")
    return ds


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-mlp")
    ap.add_argument("--algorithm", default="mtsl", choices=list_algorithms())
    ap.add_argument("--steps", type=int, default=200,
                    help="total gradient steps (rounds x local-steps)")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="local steps per round for round-based FL algorithms")
    ap.add_argument("--hp", action="append", default=[], metavar="KEY=VALUE",
                    help="algorithm hyper-parameter override (repeatable); "
                         "any scalar HParams field, e.g. --hp prox_mu=0.1 "
                         "--hp num_clusters=3 --hp sample_weighted=true. "
                         "Registry-driven: newly registered algorithms' "
                         "knobs need no new launcher flags")
    ap.add_argument("--prox-mu", type=float, default=None,
                    help="DEPRECATED alias for --hp prox_mu=...")
    ap.add_argument("--momentum", type=float, default=None,
                    help="DEPRECATED alias for --hp momentum=...")
    ap.add_argument("--num-clusters", type=int, default=None,
                    help="DEPRECATED alias for --hp num_clusters=...")
    ap.add_argument("--topology", default=None,
                    choices=[t.replace("_", "-") for t in TOPOLOGIES],
                    help="deploy on an explicit edge graph (core/topology.py)"
                         " and report the simulated wall-clock per round")
    ap.add_argument("--num-servers", type=int, default=2,
                    help="edge servers for clustered/hierarchical/"
                         "multi-server topologies")
    ap.add_argument("--uplink-mbps", type=float, default=None,
                    help="client->server bandwidth (default: infinite)")
    ap.add_argument("--downlink-mbps", type=float, default=None,
                    help="server->client bandwidth (default: infinite)")
    ap.add_argument("--backbone-mbps", type=float, default=None,
                    help="server<->server/core bandwidth (default: infinite)")
    ap.add_argument("--link-latency-ms", type=float, default=0.0,
                    help="one-way latency applied to every declared link")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="multi-server replica sync period, in rounds")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="event-driven asynchronous execution "
                         "(train/events.py): replace the synchronous round "
                         "barrier with the staleness-aware event-queue "
                         "engine — fast clients keep cycling while "
                         "stragglers' updates arrive late and merge "
                         "down-weighted by staleness")
    ap.add_argument("--staleness-decay", type=float, default=1.0,
                    help="async staleness decay: an update dispatched s "
                         "server applies ago merges with weight decay**s "
                         "(1.0 = no down-weighting)")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="async: drop updates staler than this many server "
                         "applies (default: keep all)")
    ap.add_argument("--sim-ms-per-sample", type=float, default=1.0,
                    help="simulated client compute per sample at capability "
                         "1.0 (the walltime model's compute unit)")
    ap.add_argument("--participation-rate", type=float, default=1.0,
                    help="per-round client participation probability "
                         "(1.0 = classic full synchronous rounds)")
    ap.add_argument("--straggler-frac", type=float, default=0.0,
                    help="fraction of clients that are slow devices and "
                         "complete only part of each round's local steps")
    ap.add_argument("--schedule-seed", type=int, default=None,
                    help="seed for the participation/straggler stream "
                         "(default: --seed)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="async round pipeline depth (train/pipeline.py): "
                         "schedules/batches for this many rounds are drawn "
                         "on a background thread and staged on device while "
                         "the current round runs, and metrics materialize "
                         "lazily. 0 = fully synchronous (trajectory is "
                         "identical either way)")
    ap.add_argument("--capability-batching", action="store_true",
                    help="capability-aware LOCAL batch sizing: slow clients "
                         "get proportionally smaller per-step microbatches "
                         "(per-round total sample count conserved) instead "
                         "of dropping local steps; see core/schedule.py")
    ap.add_argument("--batch-boost", type=float, default=2.0,
                    help="padded-row headroom for capability batching: fast "
                         "clients may receive up to boost x "
                         "--batch-per-client samples per step")
    ap.add_argument("--num-clients", type=int, default=None,
                    help="override the arch config's M (client scale-out "
                         "sweeps; with a classifier arch the task count "
                         "then decouples from the class count — task m's "
                         "main class is m %% num_classes)")
    ap.add_argument("--batch-per-client", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--alpha", type=float, default=0.0, help="heterogeneity")
    ap.add_argument("--noise-sigma", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--server-lr-scale", type=float, default=None)
    ap.add_argument("--optimizer", default=None, choices=[None, "sgd", "adamw"])
    ap.add_argument("--mesh", default=None, metavar="data=N[,model=K[,pod=P]]",
                    help="shard the client axis over a device mesh "
                         "(launch/mesh.py); client leaves split over the "
                         "('pod','data') axes, everything else replicates. "
                         "Emulate devices on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--client-chunk", type=int, default=None,
                    help="scan-over-clients block size: rounds process the "
                         "client axis in chunks of this many clients, so "
                         "compile time/memory stay flat as --arch's client "
                         "count grows; must divide num-clients (and be a "
                         "multiple of the mesh's client-shard count)")
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "cached"],
                    help="data path: 'synthetic' re-synthesizes every "
                         "round's batch on the host; 'cached' reads "
                         "deterministic mmap'd shards from --cache-dir "
                         "(data/shards.py — built on first use if missing; "
                         "the background thread then stays off the "
                         "critical path at massive M)")
    ap.add_argument("--cache-dir", default=None,
                    help="cache directory for --data cached (see "
                         "tools/cache_dataset.py for offline builds)")
    ap.add_argument("--dirichlet-alpha", type=float, default=None,
                    help="with --data cached: build the cache as a "
                         "Dirichlet(alpha) non-IID partition of a pooled "
                         "corpus (the FedProx/ParallelSFL heterogeneity "
                         "protocol) instead of per-client streams; small "
                         "alpha = near-disjoint client label distributions")
    ap.add_argument("--cache-examples", type=int, default=512,
                    help="examples per client materialized when the cache "
                         "is built on first use (--data cached)")
    ap.add_argument("--vectorized-data", action="store_true",
                    help="draw each round's synthetic batch with ONE batched "
                         "numpy RNG pass across all clients (host cost per "
                         "client flat in M) instead of the per-client loop; "
                         "same distribution, different seeded stream")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU tests); default is "
                         "the registered config at its published widths")
    ap.add_argument("--log-every", type=int, default=20,
                    help="log (and record in history) every N rounds; the "
                         "first and last round always log")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the registered config at its published widths unless --smoke
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.num_clients is not None:
        cfg = cfg.with_updates(num_clients=args.num_clients)
    M = cfg.num_clients
    # fail fast on client-axis divisibility BEFORE paying for model build /
    # data synthesis (shard_round_fn would raise the same constraint later)
    if args.client_chunk is not None and M % args.client_chunk != 0:
        raise SystemExit(
            f"--client-chunk {args.client_chunk} must divide the client "
            f"count: {M} % {args.client_chunk} != 0 (pick a chunk that "
            f"divides num-clients, or adjust --num-clients)")
    if args.mesh:
        sizes = parse_mesh_spec(args.mesh)
        shards = sizes.get("pod", 1) * sizes.get("data", 1)
        if shards > 1 and M % shards != 0:
            raise SystemExit(
                f"--mesh {args.mesh!r} shards the client axis {shards} "
                f"ways, which must divide the client count: {M} % {shards} "
                f"!= 0 (adjust --num-clients or the data/pod axis sizes)")
    if args.async_mode and (args.mesh or args.client_chunk is not None):
        raise SystemExit(
            "--async is incompatible with --mesh/--client-chunk: the event "
            "engine dispatches host-driven cohorts, not one sharded round "
            "program")
    model = build_model(cfg)
    is_classifier = cfg.family in ("mlp", "resnet")

    opt_name = args.optimizer or ("sgd" if is_classifier else "adamw")
    opt = sgd(args.lr) if opt_name == "sgd" else adamw(args.lr)

    alg = get_algorithm(args.algorithm)
    if not alg.uses_optimizer and opt_name != "sgd":
        print(f"note: {args.algorithm!r} runs the papers' plain local SGD at "
              f"--lr; --optimizer {opt_name} is ignored")

    scfg = ScheduleConfig(
        participation_rate=args.participation_rate,
        straggler_frac=args.straggler_frac,
        seed=args.seed if args.schedule_seed is None else args.schedule_seed,
        capability_batching=args.capability_batching,
        batch_boost=args.batch_boost)

    # registry-driven hyper-parameters: --hp key=value, with the historic
    # per-algorithm flags folded in as deprecated aliases (--hp wins)
    hp_overrides = parse_hp_overrides(args.hp)
    for flag, key in (("--prox-mu", "prox_mu"), ("--momentum", "momentum"),
                      ("--num-clusters", "num_clusters")):
        val = getattr(args, key)
        if val is not None:
            print(f"note: {flag} is deprecated; use --hp {key}={val}")
            hp_overrides.setdefault(key, val)

    topo = None
    if args.topology is not None:
        lat = args.link_latency_ms * 1e-3
        topo = build_topology(
            args.topology, M, num_servers=args.num_servers,
            uplink=mbps(args.uplink_mbps or 0.0, lat),
            downlink=mbps(args.downlink_mbps or 0.0, lat),
            backbone=mbps(args.backbone_mbps or 0.0, lat),
            sync_every=args.sync_every)

    spr = alg.steps_per_round(
        HParams(local_steps=args.local_steps).with_updates(**hp_overrides))
    rounds = num_rounds(args.steps, spr)
    # capability batching pads the generated rows so fast clients have
    # headroom; the nominal per-step batch still sets the round total
    per_round_batch = padded_batch_per_client(scfg, args.batch_per_client) * spr

    # as_numpy: batch synthesis stays host-side so the async pipeline's
    # background thread owns it; the pipeline stages arrays on device
    if is_classifier:
        # the paper ties one task to one class (num_classes == M); an
        # explicit --num-clients decouples them via num_tasks so M can
        # scale past the model's head width
        src = MultiTaskImageSource(
            num_classes=M if args.num_clients is None else cfg.num_classes,
            num_tasks=None if args.num_clients is None else M,
            image_size=cfg.image_size,
            channels=cfg.image_channels, alpha=args.alpha,
            noise_sigma=args.noise_sigma, seed=args.seed,
        )
    else:
        src = MultiTaskLMSource(vocab_size=cfg.vocab_size, num_clients=M,
                                beta=1.0 - args.alpha, seed=args.seed)
    if args.data == "cached":
        # cached shard READS replace per-round synthesis on the prefetch
        # thread (data/shards.py); the cache is built once on first use
        ds = _cached_dataset(args, src, M, is_classifier)
        batches = client_batches(
            ds, per_round_batch, steps=rounds,
            seq_len=None if is_classifier else args.seq_len,
            seed=args.seed, as_numpy=args.prefetch > 0)
    else:
        batches = client_batches(
            src, per_round_batch, steps=rounds,
            seq_len=None if is_classifier else args.seq_len,
            seed=args.seed, as_numpy=args.prefetch > 0,
            vectorized=args.vectorized_data)

    mesh = make_mesh_from_spec(args.mesh)

    # round-based algorithms ignore component_lr; mtsl applies it (Eq. 9)
    clr = lr_policy.server_scaled(M, args.server_lr_scale)
    tcfg = TrainConfig(steps=args.steps, algorithm=args.algorithm,
                       lr=args.lr, local_steps=args.local_steps,
                       log_every=args.log_every,
                       checkpoint_path=args.checkpoint,
                       checkpoint_every=100 if args.checkpoint else 0,
                       seed=args.seed,
                       hp_overrides=hp_overrides,
                       schedule=scfg,
                       prefetch=args.prefetch,
                       batch_per_client=args.batch_per_client,
                       topology=topo,
                       time_per_sample_s=args.sim_ms_per_sample * 1e-3,
                       mesh=mesh,
                       client_chunk=args.client_chunk,
                       async_mode=args.async_mode,
                       staleness_decay=args.staleness_decay,
                       max_staleness=args.max_staleness)
    state, history = train(model, opt, batches, tcfg, M, component_lr=clr)
    print(f"final loss: {history[-1]['loss']:.4f}")
    if history and (topo is not None or args.async_mode):
        t = topo.name if topo is not None else "star"
        unit = "applies" if args.async_mode else "rounds"
        print(f"simulated wall-clock ({t}"
              + (", async" if args.async_mode else "")
              + f"): {history[-1]['sim_time']:.2f}s over "
              f"{history[-1]['round']} {unit}")
    return state, history


if __name__ == "__main__":
    enable_compilation_cache()
    main()
