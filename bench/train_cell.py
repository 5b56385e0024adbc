"""Training cells: the MTSL round through `repro.train.loop.train`.

Set-up makes the weights from the seed, builds the program's state around
them, and starts ONE `train()` call. Its round program is the one `train()`
gets from `repro.train.loop.shard_round_fn`, wrapped by `Probe`, which
watches it without changing it: the first `check_rounds` rounds are the
set-up's checked steps (the first compiles; their inputs, losses, first
gradient and parameter change are kept for the comparison), and the window
opens when the last of them is done on the device. From then on the same
call runs on, fed by `Feed`, until `seconds` have passed; the window closes
when the device has finished the last round that was fed.

`Feed` is the source iterator handed to `train()`. It times each draw from
the program's synthetic source, keeps the device at most
`in_flight_rounds` rounds behind the draws (a loop that logs its loss does
the same), and ends the stream at the deadline.

After the window the state is freed and the reference repeats the checked
steps from the same weights on the same inputs.

What differs between model families comes from the configuration's plug-in
`families/<family>.py` (harness.Cell.family), which gives:

  ref_cfg(config)          the reference's view of the configuration file
  program_want(config)     the program config's fields that must equal the
                           file's (split_layers and the dtypes are added)
  make_params(key, cfg, M) every weight in float32, in the program's tree
                           layout, from one key (cfg: ref_cfg's)
  train_round_flops(config, traffic)   model FLOPs of one round
  loss_and_grads(params, batch, cfg, cdt)   the reference's summed
                           per-client loss of a round batch and its gradient
  UNIT_AXES                optional: logical axes, beyond the client and
                           layer axes, whose slices are units (weights.py)

A traffic file's optional `mesh`, e.g. {"data": 4}, shards the round over
that many of the cell's chips (TrainConfig.mesh). The weights are then
made in place (`placement`), and the reference repeats the gathered
checked rounds placed the same way.
"""
from __future__ import annotations

import threading
import time
import types

import check
import harness
import weights


def _frozen(v):
    return tuple(map(_frozen, v)) if isinstance(v, list) else v


def program_config(config, clients, family):
    """The program's registered config with the file's `program_overrides`,
    checked against the file."""
    from repro.configs import get_config

    pc = get_config(config["registry"], smoke=config.get("smoke", False))
    pc = pc.with_updates(num_clients=clients, **{
        k: _frozen(v) for k, v in config.get("program_overrides", {}).items()})
    want = dict(family.program_want(config),
                split_layers=config["split_layers"], dtype=config["dtype"],
                param_dtype=config["param_dtype"])
    bad = {k: (getattr(pc, k), v) for k, v in want.items()
           if getattr(pc, k) != v}
    if bad:
        raise SystemExit(f"bench: the program's {config['registry']!r} "
                         f"differs from the configuration file: {bad}")
    return pc


def layout(cell):
    """The program's model for the cell, its parameter template
    (weights.template) and the unit axes read from that."""
    from repro.models.registry import build_model

    fam, M = cell.family, cell.traffic["clients"]
    model = build_model(program_config(cell.config, M, fam))
    tmpl = weights.template(model, M)
    return model, tmpl, weights.unit_axes(tmpl, getattr(fam, "UNIT_AXES", ()))


def check_layout(tmpl, params):
    """The benchmark's weights have the shapes of the program's own."""
    import jax

    from repro.utils.sharding import strip

    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), strip(tmpl))
    if have != want:
        raise SystemExit("bench: the benchmark's weights do not match the "
                         "program's parameter layout")


def cell_mesh(cell, devices):
    """The traffic's mesh over the cell's devices, or None without one."""
    import math

    spec = cell.traffic.get("mesh")
    if spec is None:
        return None
    if math.prod(spec.values()) != cell.chips:
        raise SystemExit(f"bench: the mesh {spec} of {cell.name!r} does not "
                         f"cover its {cell.chips} chips")
    from repro.launch.mesh import make_mesh

    return make_mesh(tuple(spec.values()), tuple(spec), devices=devices)


def placement(mesh):
    """Where the cell's weights and round inputs are made: the default
    device (None), or with a mesh, the towers and inputs split by client
    over all of its devices and the server replicated, so that no chip
    holds every client's tower."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    return {"towers": NamedSharding(mesh, PartitionSpec(mesh.axis_names)),
            "server": NamedSharding(mesh, PartitionSpec())}


def optimizer(spec):
    from repro.optim import adamw, sgd

    if spec["name"] == "adamw":
        return adamw(spec["lr"], b1=spec["b1"], b2=spec["b2"], eps=spec["eps"])
    return sgd(spec["lr"])


def source(cell, seed):
    """The launcher's synthetic source for the cell, as round batches."""
    from repro.data.lm import MultiTaskLMSource
    from repro.data.pipeline import client_batches
    from repro.data.synthetic import MultiTaskImageSource

    t, c = cell.traffic, cell.config
    if t["data"] == "lm":
        src = MultiTaskLMSource(vocab_size=c["vocab_size"],
                                num_clients=t["clients"],
                                beta=t["heterogeneity_beta"], seed=seed)
        return client_batches(src, t["batch_per_client"], seq_len=t["seq_len"],
                              seed=seed, as_numpy=True)
    src = MultiTaskImageSource(num_classes=t["clients"],
                               image_size=c["image_size"],
                               channels=c["image_channels"],
                               alpha=t["heterogeneity_alpha"], seed=seed)
    return client_batches(src, t["batch_per_client"], seed=seed, as_numpy=True)


class Probe:
    """Wraps the round program; records what the checks need and marks
    each round's completion for `Feed`."""

    def __init__(self, fn, after, n_check, keep):
        self.fn, self.after, self.n_check = fn, after, n_check
        self.keep = keep  # completion marks kept: more than are in flight
        self.n = 0
        self.marks = {}
        self.inputs = []
        self.cond = threading.Condition()

    def __call__(self, state, batch, schedule=None):
        import jax

        self.n += 1
        k = self.n
        if k <= self.n_check:
            self.inputs.append(jax.device_get(batch))
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = self.fn(state, batch, schedule)
        with self.cond:
            self.marks[k] = metrics["loss"]
            self.marks.pop(k - self.keep, None)
            self.cond.notify_all()
        self.after(k, state, metrics)
        return state, metrics

    def wait_done(self, k):
        if k < 1:
            return
        with self.cond:
            self.cond.wait_for(lambda: self.n >= k)
            mark = self.marks.get(k)
        if mark is not None:
            mark.block_until_ready()


class Feed:
    def __init__(self, batches, probe, in_flight):
        self.it, self.probe, self.in_flight = iter(batches), probe, in_flight
        self.k = 0
        self.deadline = None
        self.data_s = []

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        self.k += 1
        self.probe.wait_done(self.k - self.in_flight)
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            raise StopIteration
        with jax.profiler.TraceAnnotation("bench.data"):
            t0 = time.perf_counter()
            batch = next(self.it)
            dt = time.perf_counter() - t0
        if self.deadline is not None:
            self.data_s.append(dt)
        return batch


def program_checks(state, k, n_check, opt, p0_fn, axes, server_scale):
    """The program's numbers at checked step k (1-based)."""
    out = {}
    if k == 1:
        if opt["name"] == "adamw":
            out["grad"] = check.unit_norms(state.opt_state.mu, axes)
            out["grad"] = {u: v / (1 - opt["b1"]) for u, v in
                           out["grad"].items()}
        else:
            g = check.diff_norms(state.params, p0_fn(), axes,
                                 1.0 / opt["lr"])
            out["grad"] = {u: v / (server_scale if u.startswith("['server']")
                                   else 1.0) for u, v in g.items()}
    if k == n_check:
        out["update"] = check.diff_norms(state.params, p0_fn(), axes)
    return out


def reference_step(cell, axes, cdt=None):
    """The reference's jitted MTSL step: (params, opt_state, batch, k) ->
    (params, opt_state, loss, per-unit gradient norms); params and
    optimizer state are donated."""
    import jax

    from reference import mtsl as ref_mtsl

    fam, t = cell.family, cell.traffic
    rcfg = fam.ref_cfg(cell.config)
    opt = dict(t["optimizer"], server_scale=t["server_lr_scale"])
    ax = jax.tree.leaves(axes)

    def step(params, opt_state, batch, k):
        with jax.default_matmul_precision("highest"):
            loss, grads = fam.loss_and_grads(params, batch, rcfg, cdt)
        params, opt_state = ref_mtsl.apply_opt(opt, params, grads, opt_state, k)
        return params, opt_state, loss, check.norm_arrays(grads, ax)

    return jax.jit(step, donate_argnums=(0, 1)), opt


def reference_checks(cell, seed, inputs, axes, cdt=None, mesh=None):
    """The reference's numbers over the same checked steps. With a mesh,
    its weights, their optimizer state and the inputs are placed by
    `placement`, so that a cell too large for one chip's reference fits
    the cell's chips."""
    import jax
    import jax.numpy as jnp

    from reference import mtsl as ref_mtsl

    fam, M = cell.family, cell.traffic["clients"]
    rcfg = fam.ref_cfg(cell.config)
    step, opt = reference_step(cell, axes, cdt)
    where = placement(mesh)
    params = weights.make_params(fam, seed, rcfg, M, where)
    if where is not None:
        inputs = [jax.device_put(b, where["towers"]) for b in inputs]
    paths = check.paths_of(params)
    opt_state = ref_mtsl.init_opt(opt, params)
    losses, grad = [], None
    for k, batch in enumerate(inputs, 1):
        params, opt_state, loss, gn = step(params, opt_state, batch,
                                           jnp.float32(k))
        losses.append(float(loss))
        if k == 1:
            grad = check.label(paths, gn)
    del opt_state
    update = check.diff_norms(
        params, weights.make_params(fam, seed, rcfg, M, where), axes)
    return {"losses": losses, "grad": grad, "update": update}


def run(cell, args, t_start, profile_dir):
    import jax
    import jax.numpy as jnp

    import repro.train.loop as loop
    from repro.core import lr_policy
    from repro.core.mtsl import TrainState

    devices = jax.devices()[:cell.chips]
    config, t, fam = cell.config, cell.traffic, cell.family
    M, n_check = t["clients"], t["check_rounds"]
    rcfg = fam.ref_cfg(config)
    mesh = cell_mesh(cell, devices)
    where = placement(mesh)
    model, tmpl, axes = layout(cell)
    opt_spec = t["optimizer"]
    opt = optimizer(opt_spec)
    params = weights.make_params(fam, args.seed, rcfg, M, where)
    check_layout(tmpl, params)
    state0 = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    del params, tmpl

    def p0():
        return weights.make_params(fam, args.seed, rcfg, M, where)

    counter = harness.CompileCounter()
    prof = harness.Profile(profile_dir) if args.trace else None
    rec = types.SimpleNamespace(losses=[], prog={}, window_t0=None,
                                traced_rounds=None)

    def after(k, state, metrics):
        if k <= n_check:
            jax.block_until_ready(state)
            rec.losses.append(metrics["loss"])
            rec.prog.update(program_checks(
                state, k, n_check, opt_spec, p0, axes, t["server_lr_scale"]))
        if k == n_check:
            jax.block_until_ready(state)
            rec.window_t0 = time.perf_counter()
            feed.deadline = rec.window_t0 + args.seconds
            counter.active = True
            if prof is not None:
                prof.start()
        elif (prof is not None and rec.traced_rounds is None and k > n_check
              and time.perf_counter() - prof.t0 >= t["trace_seconds"]):
            jax.block_until_ready(state)
            prof.stop()
            rec.traced_rounds = k - n_check

    real = loop.shard_round_fn
    holder = {}

    def wrapped_round_fn(*a, **kw):
        keep = 2 * (t["in_flight_rounds"] + t["prefetch"])
        holder["probe"] = Probe(real(*a, **kw), after, n_check, keep)
        return holder["probe"]

    feed = Feed(source(cell, args.seed), types.SimpleNamespace(
        wait_done=lambda k: holder["probe"].wait_done(k)),
        t["in_flight_rounds"])
    tcfg = loop.TrainConfig(steps=10 ** 9, algorithm="mtsl",
                            lr=opt_spec["lr"], log_every=0,
                            seed=args.seed & 0x7FFFFFFF,
                            prefetch=t["prefetch"],
                            batch_per_client=t["batch_per_client"],
                            mesh=mesh)
    clr = lr_policy.server_scaled(M, t["server_lr_scale"])
    loop.shard_round_fn = wrapped_round_fn
    try:
        state, _ = loop.train(model, opt, feed, tcfg, M,
                                    component_lr=clr, log=lambda s: None,
                                    init_state=state0)
        del state0
        jax.block_until_ready(state)
    finally:
        loop.shard_round_fn = real
    t_end = time.perf_counter()
    counter.active = False
    if prof is not None and rec.traced_rounds is None:
        prof.stop()
        rec.traced_rounds = holder["probe"].n - n_check
    probe = holder["probe"]
    rounds = probe.n - n_check
    final_loss = float(probe.marks[probe.n])
    mem = harness.memory_peak_bytes(devices)
    del state, probe.marks
    prog = {"losses": [float(x) for x in rec.losses], **rec.prog}
    ref = reference_checks(cell, args.seed, probe.inputs, axes, mesh=mesh)
    numbers, detail = check.train_numbers(prog, ref)
    control = None
    if getattr(args, "control", False):
        ctl = reference_checks(cell, args.seed, probe.inputs, axes,
                               cdt=config["control_dtype"], mesh=mesh)
        control = check.train_numbers(ctl, ref)[0]

    per_round = M * t["batch_per_client"] * t.get("seq_len", 1)
    run_rec = types.SimpleNamespace(
        kind="train", data=t["data"], cell=cell, chips=cell.chips,
        setup_s=rec.window_t0 - t_start, window_s=t_end - rec.window_t0,
        rounds=rounds, items_per_round=per_round,
        round_flops=fam.train_round_flops(config, t),
        device_kind=devices[0].device_kind,
        data_s=feed.data_s, compiles=counter.count, trace=None,
        traced_rounds=rec.traced_rounds)
    if prof is not None:
        run_rec.trace = prof.reduce(cell.chips)
    return types.SimpleNamespace(
        run=run_rec, numbers=numbers, control=control, memory=mem,
        losses=prog["losses"],
        attempted=rounds, failed=0 if final_loss == final_loss else rounds,
        log=(f"rounds in window {rounds}, window {run_rec.window_s:.3f} s, "
             f"compiles in window {counter.count}, losses program "
             f"{prog['losses']} reference {ref['losses']}, worst units "
             f"{detail}"))
