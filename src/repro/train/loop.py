"""Training loop: drives (data -> round_fn -> metrics/eval/checkpoint) for
ANY algorithm in the registry (core/algorithms.py) — mtsl, splitfed, fedavg,
fedem, and anything registered after them — with uniform history, eval, and
checkpoint hooks.

Each iteration consumes one ROUND batch `[M, steps_per_round * b, ...]`;
`TrainConfig.steps` counts GRADIENT steps, so round-based FL algorithms run
`ceil(steps / steps_per_round)` rounds (the budget rounds UP — it is never
silently truncated; the effective step count is logged when it differs).
History entries are keyed by gradient step for cross-algorithm
comparability.

Async round pipeline (train/pipeline.py). By default the loop runs
`prefetch = TrainConfig.prefetch` (2) rounds ahead of the device on the
host side:

  * the seeded ClientSchedule stream and the round batches for rounds
    i+1..i+prefetch are drawn/generated on a background thread while the
    device runs round i, and the next round's arrays are staged with
    `jax.device_put` (double buffering) before they are needed;
  * metrics are NON-BLOCKING: at the log/eval cadence the loop pushes raw
    device values into a small ring (depth = prefetch) and only
    materializes them (`np.asarray`, the host<->device sync) when the ring
    overflows or at end of run — so a `float(loss)` never stalls the
    device mid-run. History order is always push order.

Remaining sync points: checkpoint saves (`save_algorithm_state` calls
`jax.device_get` on the state) and the final ring flush. Opt out with
`prefetch=0` (`--prefetch 0` on the launcher): the loop then generates,
transfers, and materializes synchronously. Any prefetch depth is
trajectory-identical — the round math and its input order are unchanged
(pinned by the parity suite in tests/test_pipeline.py).

Client participation & compute heterogeneity (core/schedule.py): every
round the loop draws a seeded ClientSchedule from `TrainConfig.schedule`
(which clients participate, how many local steps each completes) and feeds
it to the jitted round_fn. The default config is all-clients/full-budget —
trajectory-identical to scheduling-free rounds. When the config is
heterogeneous, the capability profile is also handed to the algorithm via
HParams.capability (ParallelSFL clusters similar-capability clients).
With `ScheduleConfig.capability_batching` the schedule additionally
carries per-client per-step microbatch sizes (slow clients get smaller
batches, round total conserved); `TrainConfig.batch_per_client` must then
be set to the nominal per-step batch so the loop can apportion sizes, and
`batches` must yield padded rounds (`schedule.padded_batch_per_client`).

Edge topology & simulated wall-clock (core/topology.py): set
`TrainConfig.topology` to an explicit client/server/link graph (star,
clustered, hierarchical, multi_server) and every round's traffic — the
algorithm's `round_events` — is billed on it: history entries carry
"sim_time", the cumulative simulated seconds combining per-client compute
(capability x local steps x microbatch, `time_per_sample_s`) with per-link
transfer time (bytes/bandwidth + latency; max over parallel paths, sum
over serial phases). A topology carrying an explicit capability profile
overrides the schedule's drawn one. The trajectory itself is unchanged —
the topology is a simulation overlay.

Checkpoint/resume: pass `init_state=` (a state restored via
`load_algorithm_state`) and `start_round=` (the checkpoint's "round"
extra) to continue a run mid-stream — the schedule stream, step keys, and
checkpoint cadence all resume at the absolute round index, so an
interrupted run's trajectory matches an uninterrupted one (the caller must
supply the REMAINING round batches). Under a topology the checkpoint
extra also records "sim_time", the simulated clock at the save; pass it
back as `start_sim_time=` so the resumed history's "sim_time" continues
the uninterrupted run's cumulative clock instead of restarting at 0.

The round driver is jitted with donate_argnums=(0,) where the backend
supports donation, so state buffers are reused across rounds instead of
reallocated (see core.algorithms.jit_round_fn).
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax

from repro.core import comm_cost
from repro.core.algorithms import (
    HParams,
    get_algorithm,
    num_rounds,
    place_algorithm_state,
    shard_round_fn,
    simulate_round_walltime,
)
from repro.core.client_axis import client_axis
from repro.utils.sharding import client_sharding
from repro.core.schedule import (
    ScheduleConfig,
    capability_profile,
    full_schedule,
    schedule_stream,
)
from repro.core.topology import Topology, star
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer
from repro.optim.per_component import ComponentLR
from repro.train.checkpoint import save_algorithm_state
from repro.train.events import EventEngine
from repro.train.pipeline import MetricsRing, pipeline_rounds


@dataclass
class TrainConfig:
    steps: int = 200  # total gradient steps (rounds = steps / steps_per_round)
    algorithm: str = "mtsl"
    lr: float = 0.1  # used by round-based algorithms (mtsl uses `optimizer`)
    local_steps: int = 1  # local steps per round for round-based FL
    log_every: int = 20  # in rounds; 0 = log only the first/last round
    eval_every: int = 0  # in rounds; 0 disables eval
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0  # in rounds
    microbatches: int = 1
    seed: int = 0
    # DEPRECATED per-algorithm knobs: prefer hp_overrides (the launcher's
    # registry-driven --hp path). Still honored, with hp_overrides winning
    # when both set the same HParams field.
    prox_mu: float = 0.01  # fedprox proximal strength
    momentum: float = 0.9  # smofi server-side momentum
    num_clusters: int = 2  # parallelsfl cluster count
    # client participation / straggler simulation; the default is the
    # classic full synchronous round (see core/schedule.py)
    schedule: ScheduleConfig = field(default_factory=ScheduleConfig)
    # async round pipeline depth (train/pipeline.py): how many rounds of
    # schedules/batches the host runs ahead, and how many logged rounds of
    # metrics may stay un-materialized in flight. 0 = fully synchronous.
    prefetch: int = 2
    # nominal per-step batch per client; required when
    # schedule.capability_batching is on (sizes are apportioned from it)
    batch_per_client: Optional[int] = None
    # explicit edge deployment graph (core/topology.py). When set, the loop
    # bills each round's TrafficEvents on it and history entries carry
    # "sim_time" — the cumulative SIMULATED wall-clock (per-client compute
    # + per-link transfer, see topology.round_walltime). A topology with an
    # explicit capability profile also overrides the schedule's drawn one.
    # The training math itself is unchanged (the topology is a simulation
    # overlay for placement, billing, and the clock).
    topology: Optional[Topology] = None
    # simulated seconds of client compute per sample at capability 1.0
    time_per_sample_s: float = 1e-3
    # registry-driven HParams overrides (the launcher's --hp key=value
    # group); applied over the HParams assembled from the fields above
    hp_overrides: dict = field(default_factory=dict)
    # massive-M client scale-out (core/client_axis.py, shard_round_fn).
    # mesh: a jax Mesh whose client axes (("pod","data")) shard every
    # leading-client-axis leaf — state (per alg.client_axes), the staged
    # round batches, and the schedule rows; cross-client reductions lower
    # to all-reduces. None = single-device (bit-identical to the goldens).
    mesh: Optional[object] = None
    # client_chunk: run each round's per-client block as a lax.scan over
    # chunks of this many clients — flat compile time/memory as M grows.
    # Must divide num_clients (and be a multiple of the mesh's client-shard
    # count when both are set). None = plain vmap.
    client_chunk: Optional[int] = None
    # event-driven asynchronous execution (train/events.py): replace the
    # synchronous round barrier with the staleness-aware event-queue
    # engine. Each dispatch still consumes one round batch + one schedule
    # draw, so `steps` bounds the same total work; history entries are
    # keyed by server APPLY events instead of rounds. Incompatible with
    # mesh/client_chunk (the engine is host-driven per cohort).
    async_mode: bool = False
    # FedAsync staleness decay: an update dispatched s applies ago merges
    # with weight decay**s. 1.0 = no down-weighting.
    staleness_decay: float = 1.0
    # drop updates staler than this many applies (None = keep all)
    max_staleness: Optional[int] = None


def train(
    model: Model,
    optimizer: Optimizer,
    batches,
    tcfg: TrainConfig,
    num_clients: int,
    component_lr: Optional[ComponentLR] = None,
    eval_batches=None,
    log: Callable[[str], None] = print,
    init_state=None,
    start_round: int = 0,
    init_events: Optional[dict] = None,
    start_sim_time: float = 0.0,
):
    """Returns (final_state, history list of metric dicts).

    `batches` must yield round batches `[M, steps_per_round * b, ...]`
    (for single-step algorithms that is the ordinary per-step batch).
    History entries carry the round's participant count under
    "participants". `init_state`/`start_round` resume a checkpointed run
    (see module docstring).
    """
    alg = get_algorithm(tcfg.algorithm)
    scfg = tcfg.schedule or ScheduleConfig()
    if scfg.capability_batching and tcfg.batch_per_client is None:
        raise ValueError(
            "ScheduleConfig.capability_batching needs "
            "TrainConfig.batch_per_client (the nominal per-step batch) to "
            "apportion per-client microbatch sizes")
    cap = capability_profile(num_clients, scfg, tcfg.topology)
    hp = HParams(lr=tcfg.lr, local_steps=tcfg.local_steps,
                 optimizer=optimizer, component_lr=component_lr,
                 microbatches=tcfg.microbatches, prox_mu=tcfg.prox_mu,
                 momentum=tcfg.momentum, num_clusters=tcfg.num_clusters,
                 sample_weighted=scfg.sample_weighted,
                 capability=None if scfg.is_trivial else tuple(cap))
    if tcfg.hp_overrides:
        hp = hp.with_updates(**tcfg.hp_overrides)
    spr = alg.steps_per_round(hp)
    rounds = num_rounds(tcfg.steps, spr)
    if rounds * spr != tcfg.steps:
        log(f"note: {tcfg.steps} requested steps round UP to {rounds} rounds "
            f"x {spr} steps/round = {rounds * spr} effective gradient steps")

    rng = jax.random.PRNGKey(tcfg.seed)
    state = (alg.init_state(model, rng, num_clients, hp)
             if init_state is None else init_state)
    if tcfg.async_mode:
        if tcfg.mesh is not None or tcfg.client_chunk is not None:
            raise ValueError(
                "async_mode is incompatible with mesh/client_chunk: the "
                "event engine dispatches host-driven cohorts, not a single "
                "sharded round program")
        return _train_async(model, tcfg, num_clients, alg, hp, scfg, cap,
                            spr, rounds, state, batches, eval_batches, log,
                            init_events)
    if tcfg.mesh is not None:
        # split the client axis of the state over the mesh up front so the
        # first round starts from device-resident shards
        state = place_algorithm_state(alg, state, tcfg.mesh)
    round_fn = shard_round_fn(alg, model, num_clients, hp,
                              mesh=tcfg.mesh, client_chunk=tcfg.client_chunk)

    def _jit_eval():
        ev = alg.eval_fn(model, num_clients)
        if tcfg.mesh is None and tcfg.client_chunk is None:
            return jax.jit(ev)

        def ev_ctx(state, batch):
            with client_axis(chunk=tcfg.client_chunk):
                return ev(state, batch)

        return jax.jit(ev_ctx)

    eval_fn = _jit_eval() if eval_batches else None
    # ONE cycling iterator for the whole run: a list of eval batches is
    # rotated through (not stuck on its first element), and a generator is
    # consumed once then replayed instead of being drained mid-run. On
    # resume, skip the evals the interrupted run already consumed so the
    # stream position matches an uninterrupted run's.
    eval_iter = itertools.cycle(eval_batches) if eval_fn is not None else None
    if eval_iter is not None and start_round and tcfg.eval_every:
        for _ in range(start_round // tcfg.eval_every):
            next(eval_iter)

    # the per-round schedule stream, resumable at start_round; trivial
    # configs reuse one constant schedule (no per-round allocation)
    if scfg.is_trivial:
        sched_iter = itertools.repeat(full_schedule(num_clients, spr))
    else:
        sched_iter = schedule_stream(scfg, num_clients, spr,
                                     tcfg.batch_per_client, start_round)

    # simulated wall-clock (core/topology.py): bill each round's traffic
    # events on the explicit deployment graph and accumulate the simulated
    # clock (resuming from start_sim_time) alongside the real one
    topo = tcfg.topology
    round_sim_s = None
    if topo is not None:
        if topo.capability is None:
            topo = topo.with_capability(cap)
        tower_p, total_p = comm_cost.model_param_counts(model)

        def round_sim_s(r, b, sched):
            # b: per-step row width as generated (padded under capability
            # batching; sizes then carry the true per-client sample counts)
            return simulate_round_walltime(
                alg, topo, model.cfg, num_clients, b, hp, sched,
                tower_params=tower_p, total_params=total_p,
                time_per_sample_s=tcfg.time_per_sample_s,
                round_idx=r, local_steps=spr)

    history = []
    # wall-clock is reporting-only (history["time"]), never trajectory
    t0 = time.time()  # repro-lint: allow(nondeterminism)
    # the simulated clock resumes at the checkpoint's value (extra
    # ["sim_time"]): a resumed run's "sim_time" history must continue the
    # uninterrupted run's cumulative clock, not restart at 0
    sim_time = float(start_sim_time)

    def _sink(p):
        entry = {"step": p["step"], "round": p["round"],
                 "loss": float(p["metrics"]["loss"]),
                 "time": p["time"],
                 "participants": p["participants"]}
        if "sim_time" in p:
            entry["sim_time"] = p["sim_time"]
        if "eval" in p:
            entry["acc_mtl"] = float(p["eval"].get("acc_mtl", float("nan")))
        history.append(entry)
        if p["do_log"]:
            log(f"step {entry['step']:>6d}  loss {entry['loss']:.4f}"
                + (f"  acc_mtl {entry['acc_mtl']:.3f}" if "acc_mtl" in entry else "")
                + f"  ({entry['time']:.1f}s)")

    ring = MetricsRing(tcfg.prefetch, _sink)
    rounds_done = ckpt_round = start_round
    remaining = max(rounds - start_round, 0)
    # with a mesh, prefetched batches are staged directly onto their client
    # shards (per-device slices of the leading axis) instead of device 0
    stage_sharding = (client_sharding(tcfg.mesh)
                      if tcfg.mesh is not None else None)
    pairs = pipeline_rounds(batches, sched_iter, depth=tcfg.prefetch,
                            num_rounds=remaining, device=stage_sharding)
    for r, (batch, sched) in _traced_rounds(pairs, start_round, remaining):
        # read the batch's static width BEFORE dispatch: the sharded round
        # program donates the staged batch buffers on non-CPU backends
        b = (jax.tree.leaves(batch)[0].shape[1] // spr
             if round_sim_s is not None else None)
        with jax.profiler.TraceAnnotation("repro.dispatch", round=r):
            state, metrics = round_fn(state, batch, sched)
        rounds_done = r
        if round_sim_s is not None:
            sim_time += round_sim_s(r, b, sched)
        # log_every=0 disables the periodic cadence (first/last still log),
        # mirroring eval_every=0 — and never divides by zero. The
        # unconditional first-round log belongs to FRESH runs only: a
        # resumed run must not record rounds an uninterrupted one would
        # skip (resume == uninterrupted, entry for entry)
        do_log = ((tcfg.log_every and r % tcfg.log_every == 0)
                  or r == 1 or r == rounds)
        # eval runs on its OWN cadence — never gated behind the log cadence —
        # and its history entry is recorded unconditionally. The run's LAST
        # round always evals when eval is configured (matching _train_async
        # and benchmarks/common.run_algorithm): benchmarks read final
        # accuracy from the tail entry, which must not depend on whether
        # the round count happens to land on the cadence
        do_eval = (eval_fn is not None and tcfg.eval_every
                   and (r % tcfg.eval_every == 0 or r == rounds))
        if do_log or do_eval:
            # stamp the elapsed time NOW (when the round was dispatched) —
            # the ring materializes entries up to `prefetch` rounds later
            payload = {"metrics": metrics, "step": r * spr, "round": r,
                       "participants": sched.num_participants,
                       # reporting-only  # repro-lint: allow(nondeterminism)
                       "time": time.time() - t0, "do_log": do_log}
            if round_sim_s is not None:
                payload["sim_time"] = sim_time
            if do_eval:
                payload["eval"] = eval_fn(state, next(eval_iter))
            ring.push(payload)
        if tcfg.checkpoint_path and tcfg.checkpoint_every and r % tcfg.checkpoint_every == 0:
            extra = {"step": r * spr, "round": r}
            if round_sim_s is not None:
                # record the simulated clock so a resumed run can continue
                # it (start_sim_time=) instead of restarting at 0
                extra["sim_time"] = sim_time
            save_algorithm_state(tcfg.checkpoint_path, alg, state,
                                 extra=extra)
            ckpt_round = r
    pairs.close()
    ring.flush()
    if tcfg.checkpoint_path and rounds_done > ckpt_round:
        # always leave a final checkpoint behind (unless the last round's
        # periodic save already wrote this exact state)
        extra = {"step": rounds_done * spr, "round": rounds_done}
        if round_sim_s is not None:
            extra["sim_time"] = sim_time
        save_algorithm_state(tcfg.checkpoint_path, alg, state, extra=extra)
    return state, history


def _traced_rounds(pairs, start_round: int, remaining: int):
    """Yield `(r, (batch, schedule))` for the absolute 1-based rounds after
    `start_round`, under profiler spans, which cost microseconds when no
    trace runs. Round r's `repro.round` span stays open while the caller's
    loop body runs (this generator is suspended inside it); within it,
    `repro.input_wait` covers the take of the staged pair, with `queued`
    the pairs the producer thread had ready."""
    for r in range(start_round + 1, start_round + remaining + 1):
        with jax.profiler.StepTraceAnnotation("repro.round", step_num=r,
                                              round=r):
            with jax.profiler.TraceAnnotation(
                    "repro.input_wait", round=r, queued=pairs.queued()):
                pair = next(pairs, None)
            if pair is None:
                return
            yield r, pair


def _train_async(model, tcfg, num_clients, alg, hp, scfg, cap, spr, rounds,
                 state, batches, eval_batches, log, init_events):
    """The event-driven branch of train(): drives the EventEngine
    (train/events.py) instead of the barrier loop.

    One cohort dispatch consumes one round batch + one schedule draw, so
    `TrainConfig.steps` bounds the same total work as the synchronous
    path; history/eval/checkpoint cadences are counted in server APPLY
    events ("round" in history = apply index). Checkpoints carry the
    engine clock under extra["events"]; resume by passing the restored
    state as `init_state=` and that snapshot as `init_events=` together
    with the batch stream positioned at snapshot["dispatches"] rounds in.
    """
    topo = tcfg.topology if tcfg.topology is not None else star(num_clients)
    if topo.capability is None:
        topo = topo.with_capability(cap)
    engine = EventEngine(alg, model, num_clients, hp, topo,
                         staleness_decay=tcfg.staleness_decay,
                         max_staleness=tcfg.max_staleness,
                         time_per_sample_s=tcfg.time_per_sample_s,
                         init_state=state, snapshot=init_events)
    start_disp = engine.dispatches
    if scfg.is_trivial:
        sched_iter = itertools.repeat(full_schedule(num_clients, spr))
    else:
        sched_iter = schedule_stream(scfg, num_clients, spr,
                                     tcfg.batch_per_client, start_disp)
    eval_fn = (jax.jit(alg.eval_fn(model, num_clients))
               if eval_batches else None)
    eval_iter = itertools.cycle(eval_batches) if eval_fn is not None else None
    if eval_iter is not None and engine.applies and tcfg.eval_every:
        # resume: skip the evals the interrupted run already consumed
        for _ in range(engine.applies // tcfg.eval_every):
            next(eval_iter)
    # the same host-side prefetch pipeline as the sync path stages batches
    # and schedule draws ahead of the engine's dispatch demand
    pairs = pipeline_rounds(batches, sched_iter, depth=tcfg.prefetch,
                            num_rounds=max(rounds - start_disp, 0))

    history = []
    # wall-clock is reporting-only (history["time"]), never trajectory
    t0 = time.time()  # repro-lint: allow(nondeterminism)
    ckpt_applies = engine.applies
    last_ev = None

    def _entry(ev):
        e = {"step": ev["applies"] * spr, "round": ev["applies"],
             "loss": float(ev["metrics"]["loss"]),
             # reporting-only  # repro-lint: allow(nondeterminism)
             "time": time.time() - t0,
             "participants": ev["participants"],
             "sim_time": ev["sim_time"], "staleness": ev["staleness"]}
        return e

    def _log(e):
        log(f"apply {e['round']:>6d}  loss {e['loss']:.4f}"
            + (f"  acc_mtl {e['acc_mtl']:.3f}" if "acc_mtl" in e else "")
            + f"  (sim {e['sim_time']:.3f}s, stale {e['staleness']})")

    for ev in engine.run(pairs, max_dispatches=rounds):
        if ev["metrics"] is None:
            continue  # staleness-dropped or participant-free arrival
        last_ev = ev
        a_i = ev["applies"]
        do_log = bool(tcfg.log_every and a_i % tcfg.log_every == 0)
        do_eval = bool(eval_fn is not None and tcfg.eval_every
                       and a_i % tcfg.eval_every == 0)
        if do_log or do_eval:
            e = _entry(ev)
            if do_eval:
                e["acc_mtl"] = float(eval_fn(engine.state(), next(eval_iter))
                                     .get("acc_mtl", float("nan")))
            history.append(e)
            if do_log:
                _log(e)
        if (tcfg.checkpoint_path and tcfg.checkpoint_every
                and a_i % tcfg.checkpoint_every == 0):
            snap = engine.snapshot()
            save_algorithm_state(
                tcfg.checkpoint_path, alg, engine.state(),
                # "sim_time" mirrors the sync path's extra (the engine
                # restores its own clock from the snapshot on resume)
                extra={"step": a_i * spr, "round": a_i,
                       "sim_time": snap["sim_time"], "events": snap})
            ckpt_applies = a_i
    final_state = engine.state()
    if last_ev is not None and (not history
                                or history[-1]["round"] != last_ev["applies"]):
        # mirror the sync loop: the run's last applied event always lands
        # in history (with a final eval when eval is configured)
        e = _entry(last_ev)
        if eval_fn is not None:
            e["acc_mtl"] = float(eval_fn(final_state, next(eval_iter))
                                 .get("acc_mtl", float("nan")))
        history.append(e)
        _log(e)
    if tcfg.checkpoint_path and engine.applies > ckpt_applies:
        snap = engine.snapshot()
        save_algorithm_state(
            tcfg.checkpoint_path, alg, final_state,
            extra={"step": engine.applies * spr, "round": engine.applies,
                   "sim_time": snap["sim_time"], "events": snap})
    return final_state, history
