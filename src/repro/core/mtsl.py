"""MTSL train/eval step builders — the paper's Alg. 1 as pjit-able JAX.

One jitted `train_step` realizes the whole round:
  * client towers run vmapped over the leading client axis (sharded over
    ("pod","data") -> zero-communication private compute),
  * the smashed-data upload is the activation boundary (client dim folds
    into batch),
  * the server stack runs on all clients' smashed data; pjit inserts ONE
    all-reduce over the client axis for server grads only — the paper's
    implicit aggregation,
  * per-component learning rates (eta_s, eta_1..eta_M) apply via the
    ComponentLR wrapper (optim/per_component.py).

`algorithm` selects the sync policy (core/federation.py): "mtsl" (none),
"splitfed" (federate towers), "fedavg" (federate everything). FedEM has its
own builder in federation.py (mixture of K full models).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import client_axis
from repro.core import federation
from repro.core import schedule as schedule_mod
from repro.core.split import is_client_path, stack_towers, replicate_tower
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer, apply_updates
from repro.optim.per_component import ComponentLR, per_component_lr

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree  # {"towers": [M,...], "server": ...}
    opt_state: PyTree
    step: jax.Array


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def _ce_logits(logits, labels, mask=None, denom=None):
    """Mean cross-entropy; logits [..., V] f32, labels int. `mask`
    optionally selects live samples; `denom` overrides the masked mean's
    denominator (gradient accumulation splits one live-sample mean across
    microbatches — each slice contributes its masked SUM over the caller's
    shared denominator so the accumulated total is the true mean)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        d = jnp.maximum(jnp.sum(mask), 1.0) if denom is None else denom
        return jnp.sum(nll * mask) / d
    return jnp.mean(nll)


def _lm_loss(logits, tokens, smask=None, denom=None):
    """Next-token CE. logits/tokens: [..., S(,V)]. `smask` [b] optionally
    selects the live sequences of a padded batch (capability batch sizing);
    `denom` is the _ce_logits denominator override in TOKENS."""
    mask = jnp.ones(tokens[..., 1:].shape, jnp.float32)
    if smask is not None:
        mask = mask * smask.reshape(smask.shape + (1,) * (mask.ndim - smask.ndim))
    return _ce_logits(logits[..., :-1, :], tokens[..., 1:], mask=mask,
                      denom=denom)


def make_loss_fn(model: Model, num_clients: int) -> Callable:
    """loss_fn(params, batch, participation=None, sample_mask=None)
    -> (loss, metrics).

    batch entries carry a leading client axis [M, b, ...]:
      LM: {"tokens"} (+"vis" | +"frames"); classifiers: {"image","label"}.
    Loss = sum over tasks of per-task mean loss (paper Eq. 2). An optional
    `participation` mask [M] of {0,1} weights the per-task sum AND stops
    gradient through masked-out clients' smashed activations — a
    masked-out client's tower receives zero gradient (including through
    any auxiliary losses, e.g. the MoE router balance term) and the server
    sees only participants' TASK gradients. Known limitation: a batch-level
    auxiliary loss (MoE router balance) is computed over ALL clients'
    smashed tokens, so non-participants' token values still contribute to
    the aux value and to its gradient into SERVER params; severing that
    would need a per-client aux decomposition from server_forward. Exact
    for classifier families (aux = 0, the paper's experiments). All-ones
    is bit-identical to no mask.

    Under an ambient `core.client_axis` context with chunk=c < M the whole
    per-client block (tower vmap + smashed fold + server forward + per-task
    reduction) runs as a `lax.scan` over M/c client chunks instead of one
    M-wide trace: compiled shapes are [c, ...] regardless of M, so compile
    time and live memory stay flat as M grows. Per-task losses, accuracy
    numerators, and gradients are accumulated across chunks, matching the
    dense trace up to floating-point reduction order (exactly, for
    classifier families where aux = 0; an MoE batch-level aux becomes a
    sum of per-chunk aux terms). The default (no context) path below is
    textually the historical dense trace — bit-identical.

    `sample_mask` (optional [M, b] {0,1}) is capability-aware batch sizing
    (core/schedule.py): client m's per-task loss becomes the mean over its
    first sizes[m] samples of a padded batch row — pad samples contribute
    neither loss nor task gradient (the MoE-aux caveat above applies to pad
    samples the same way it applies to non-participants). `sample_denom`
    (optional [M] floats) overrides the per-client masked-mean denominator
    — gradient accumulation passes each microbatch `live_samples[m] /
    microbatches` so the uniformly-averaged accumulation equals the
    whole-batch live-sample mean regardless of how the live prefix falls
    across microbatch slices.
    """
    cfg = model.cfg
    M = num_clients
    is_classifier = cfg.family in ("mlp", "resnet")

    def _chunk_terms(towers_c, server, batch_c, part_c, sm_c, sd_c, c):
        """One client chunk's forward: per-task losses [c], the chunk's
        accuracy-numerator contribution, and its aux term. Mirrors the
        dense body below with M -> c."""
        inputs = {k: v for k, v in batch_c.items() if k != "label"}
        with jax.named_scope("mtsl.tower"):
            smashed = jax.vmap(model.tower_forward)(towers_c, inputs)
        if part_c is not None:
            smashed = jax.tree.map(
                lambda s: jnp.where(
                    (part_c > 0).reshape((c,) + (1,) * (s.ndim - 1)),
                    s, jax.lax.stop_gradient(s)),
                smashed)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), smashed)
        with jax.named_scope("mtsl.server"):
            logits, aux = model.server_forward(server, flat)
        with jax.named_scope("mtsl.loss"):
            return _chunk_loss(logits, aux, batch_c, sm_c, sd_c, c)

    def _chunk_loss(logits, aux, batch_c, sm_c, sd_c, c):
        """The chunk's per-task losses and accuracy numerator."""
        if is_classifier:
            labels = batch_c["label"].reshape(-1)
            logits32 = logits.astype(jnp.float32)
            per_logits = logits32.reshape(c, -1, logits.shape[-1])
            if sm_c is None:
                per = jax.vmap(_ce_logits)(per_logits, batch_c["label"])
            elif sd_c is None:
                per = jax.vmap(_ce_logits)(per_logits, batch_c["label"], sm_c)
            else:
                per = jax.vmap(_ce_logits)(
                    per_logits, batch_c["label"], sm_c,
                    jnp.maximum(sd_c, 1e-9))
            correct = (jnp.argmax(logits32, -1) == labels).astype(jnp.float32)
            w = jnp.ones_like(correct) if sm_c is None else sm_c.reshape(-1)
            return per, jnp.sum(correct * w), aux
        per_logits = logits.astype(jnp.float32).reshape(
            (c, -1) + logits.shape[1:])
        if sm_c is None:
            per = jax.vmap(_lm_loss)(per_logits, batch_c["tokens"])
        elif sd_c is None:
            per = jax.vmap(_lm_loss)(per_logits, batch_c["tokens"], sm_c)
        else:
            seq_tokens = batch_c["tokens"].shape[-1] - 1
            per = jax.vmap(_lm_loss)(
                per_logits, batch_c["tokens"], sm_c,
                jnp.maximum(sd_c * seq_tokens, 1e-9))
        return per, jnp.zeros((), jnp.float32), aux

    def _chunked_loss(params, batch, participation, sample_mask,
                      sample_denom, c):
        if M % c:
            raise ValueError(
                f"num_clients {M} not divisible by client chunk {c}")
        n = M // c
        shard = client_axis.current_sharding()
        chunk_shard = (None if shard is None
                       else client_axis._chunk_spec_sharding(shard))

        def blk(tree):
            out = jax.tree.map(
                lambda x: x.reshape((n, c) + x.shape[1:]), tree)
            return client_axis.constrain_clients(out, chunk_shard)

        xs = {"towers": blk(params["towers"]), "batch": blk(batch)}
        if participation is not None:
            xs["part"] = participation.reshape(n, c)
        if sample_mask is not None:
            xs["sm"] = blk(sample_mask)
        if sample_denom is not None:
            xs["sd"] = sample_denom.reshape(n, c)
        server = params["server"]

        def body(carry, x):
            num, aux_acc = carry
            per_c, num_c, aux_c = _chunk_terms(
                x["towers"], server, x["batch"], x.get("part"),
                x.get("sm"), x.get("sd"), c)
            return (num + num_c, aux_acc + aux_c), per_c

        zero = jnp.zeros((), jnp.float32)
        (acc_num, aux), per_chunks = jax.lax.scan(body, (zero, zero), xs)
        per = per_chunks.reshape(M)
        per = client_axis.constrain_clients(per, shard)
        with jax.named_scope("mtsl.loss"):
            wper = per if participation is None else per * participation
            loss = jnp.sum(wper) + aux
        if not is_classifier:
            return loss, {"loss": loss, "per_task": per, "aux": aux}
        width = jax.tree.leaves(batch)[0].shape[1]
        if sample_mask is None:
            acc_den = jnp.asarray(M * width, jnp.float32)
        elif sample_denom is None:
            acc_den = jnp.maximum(jnp.sum(sample_mask), 1.0)
        else:
            acc_den = jnp.maximum(jnp.sum(sample_denom), 1e-9)
        acc = acc_num / acc_den
        return loss, {"loss": loss, "per_task": per, "acc": acc, "aux": aux}

    def loss_fn(params, batch, participation=None, sample_mask=None,
                sample_denom=None):
        chunk = client_axis.current_chunk()
        if chunk is not None and chunk < M:
            return _chunked_loss(params, batch, participation, sample_mask,
                                 sample_denom, chunk)
        inputs = {k: v for k, v in batch.items() if k != "label"}
        with jax.named_scope("mtsl.tower"):
            smashed = jax.vmap(model.tower_forward)(params["towers"], inputs)
        if participation is not None:
            # sever non-participants' backward path entirely (per-task AND
            # aux losses); where() with an all-true mask is the identity
            smashed = jax.tree.map(
                lambda s: jnp.where(
                    (participation > 0).reshape(
                        (M,) + (1,) * (s.ndim - 1)),
                    s, jax.lax.stop_gradient(s)),
                smashed)
        # --- smashed-data upload: fold client dim into batch
        flat = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), smashed
        )
        with jax.named_scope("mtsl.server"):
            logits, aux = model.server_forward(params["server"], flat)
        with jax.named_scope("mtsl.loss"):
            return _dense_loss(logits, aux, batch, participation,
                               sample_mask, sample_denom)

    def _dense_loss(logits, aux, batch, participation, sample_mask,
                    sample_denom):
        """The dense path's loss and metrics from the server's logits."""
        if is_classifier:
            labels = batch["label"].reshape(-1)
            logits32 = logits.astype(jnp.float32)
            per_logits = logits32.reshape(M, -1, logits.shape[-1])
            if sample_mask is None:
                per = jax.vmap(_ce_logits)(per_logits, batch["label"])
                acc = jnp.mean(
                    (jnp.argmax(logits32, -1) == labels).astype(jnp.float32)
                )
            else:
                if sample_denom is None:
                    per = jax.vmap(_ce_logits)(
                        per_logits, batch["label"],
                        sample_mask)  # [M] live-sample mean
                else:
                    # epsilon (not 1) guard: a size-0 client's numerator is
                    # exactly 0, and clamping to 1 would phantom-count it
                    # in the accumulated acc denominator
                    per = jax.vmap(_ce_logits)(
                        per_logits, batch["label"], sample_mask,
                        jnp.maximum(sample_denom, 1e-9))
                correct = (jnp.argmax(logits32, -1) == labels).astype(
                    jnp.float32)
                w = sample_mask.reshape(-1)
                acc_denom = (jnp.maximum(jnp.sum(w), 1.0)
                             if sample_denom is None
                             else jnp.maximum(jnp.sum(sample_denom), 1e-9))
                acc = jnp.sum(correct * w) / acc_denom
            wper = per if participation is None else per * participation
            loss = jnp.sum(wper) + aux
            return loss, {"loss": loss, "per_task": per, "acc": acc, "aux": aux}
        per_logits = logits.astype(jnp.float32).reshape(
            (M, -1) + logits.shape[1:])
        if sample_mask is None:
            per = jax.vmap(_lm_loss)(per_logits, batch["tokens"])
        elif sample_denom is None:
            per = jax.vmap(_lm_loss)(per_logits, batch["tokens"], sample_mask)
        else:
            seq_tokens = batch["tokens"].shape[-1] - 1
            per = jax.vmap(_lm_loss)(
                per_logits, batch["tokens"], sample_mask,
                jnp.maximum(sample_denom * seq_tokens, 1e-9))
        wper = per if participation is None else per * participation
        loss = jnp.sum(wper) + aux
        return loss, {"loss": loss, "per_task": per, "aux": aux}

    return loss_fn


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def init_state(
    model: Model,
    optimizer: Optimizer,
    rng,
    num_clients: int,
    algorithm: str = "mtsl",
):
    """Annotated params + opt state. FL algorithms start from a shared tower."""
    k1, k2 = jax.random.split(rng)
    stack = stack_towers if algorithm == "mtsl" else replicate_tower
    params = {
        "towers": stack(model.init_tower, k1, num_clients),
        "server": model.init_server(k2),
    }
    return params


def build_train_step(
    model: Model,
    base_optimizer: Optimizer,
    num_clients: int,
    algorithm: str = "mtsl",
    microbatches: int = 1,
) -> Callable:
    """Returns train_step(state, batch, component_lr=None, participation=None,
    sample_sizes=None) -> (state, metrics). `participation` is an optional
    [M] {0,1} mask: masked-out clients' towers get zero gradient and the
    server aggregates participants only (see make_loss_fn); None/all-ones is
    the full round. `sample_sizes` ([M] int32, capability-aware batch
    sizing) limits client m's contribution to the first sample_sizes[m]
    samples of its (padded) batch row; under gradient accumulation the
    per-row sample mask is sliced along with the batch and every microbatch
    divides by the SHARED live-sample count (live[m]/microbatches), so the
    uniformly-averaged accumulation equals the whole-batch live-sample mean
    no matter how a client's live prefix falls across the slices."""
    local_step, apply_step = build_train_phases(
        model, base_optimizer, num_clients, algorithm, microbatches)

    def train_step(state: TrainState, batch,
                   component_lr: Optional[ComponentLR] = None,
                   participation=None, sample_sizes=None):
        grads, metrics = local_step(state, batch, participation, sample_sizes)
        return apply_step(state, grads, metrics, component_lr, participation)

    return train_step


def build_train_phases(
    model: Model,
    base_optimizer: Optimizer,
    num_clients: int,
    algorithm: str = "mtsl",
    microbatches: int = 1,
) -> tuple:
    """`build_train_step` split at the smashed-gradient uplink.

    Returns (local_step, apply_step):
      local_step(state, batch, participation=None, sample_sizes=None)
          -> (grads, metrics): the whole forward/backward (including the
          microbatch accumulation scan) against the round-start state.
      apply_step(state, grads, metrics, component_lr=None,
          participation=None) -> (TrainState, metrics): the server-side
          commit — sync_transform's federation all-reduce, the optimizer
          update, participation tower-freezing, step increment.
    `build_train_step` is exactly their composition (the seeded goldens pin
    it); the event engine drives them on its own clock."""
    loss_fn = make_loss_fn(model, num_clients)
    opt = per_component_lr(base_optimizer, is_client_path)
    sync = federation.sync_transform(algorithm, num_clients)

    def _grads(params, batch, participation=None, smask=None, sdenom=None):
        return jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, participation, smask, sdenom)

    def local_step(state: TrainState, batch,
                   participation=None, sample_sizes=None):
        width = jax.tree.leaves(batch)[0].shape[1]
        smask = (None if sample_sizes is None
                 else schedule_mod.sample_mask(sample_sizes, width))
        if microbatches > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape((x.shape[0], microbatches, -1) + x.shape[2:]).swapaxes(0, 1),
                batch,
            )
            sm_mbs = (None if smask is None else
                      smask.reshape((smask.shape[0], microbatches, -1))
                      .swapaxes(0, 1))  # [mb, M, b/mb]: sliced like the batch
            # shared denominator per slice: the whole row's live count over
            # microbatches (constant across slices — see docstring).
            # Deliberately UNclamped: a masked-out client (sizes=0) must
            # contribute zero to the acc denominator too; make_loss_fn
            # guards the division with an epsilon
            sdenom = (None if sample_sizes is None else
                      sample_sizes.astype(jnp.float32) / microbatches)

            def body(carry, xs):
                mb, sm = xs if sm_mbs is not None else (xs, None)
                (loss, metrics), grads = _grads(state.params, mb,
                                                participation, sm, sdenom)
                acc_loss, acc_metrics, acc_grads = carry
                acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
                acc_metrics = jax.tree.map(jnp.add, acc_metrics, metrics)
                return (acc_loss + loss, acc_metrics, acc_grads), None

            (loss0, metrics0), g0 = _grads(
                state.params, jax.tree.map(lambda x: x[0], mbs), participation,
                None if sm_mbs is None else sm_mbs[0], sdenom
            )
            rest = jax.tree.map(lambda x: x[1:], mbs)
            (loss, metrics, grads), _ = jax.lax.scan(
                body, (loss0, metrics0, g0),
                rest if sm_mbs is None else (rest, sm_mbs[1:])
            )
            inv = 1.0 / microbatches
            grads = jax.tree.map(lambda g: g * inv, grads)
            metrics = jax.tree.map(lambda m: m * inv, metrics)
        else:
            (loss, metrics), grads = _grads(state.params, batch, participation,
                                            smask)
        return grads, metrics

    def apply_step(state: TrainState, grads, metrics,
                   component_lr: Optional[ComponentLR] = None,
                   participation=None):
        with jax.named_scope("mtsl.update"):
            return _apply(state, grads, metrics, component_lr, participation)

    def _apply(state, grads, metrics, component_lr, participation):
        grads = sync(grads)
        updates, opt_state = opt.update(
            grads, state.opt_state, state.params, state.step,
            component_lr=component_lr,
        )
        if participation is not None:
            # freeze non-participants' towers under STATEFUL optimizers
            # too: zero grads alone would not stop e.g. adam momentum from
            # moving an offline device's params. (The optimizer moments
            # themselves still tick — they live server-side.) An all-ones
            # mask multiplies through as the identity.
            updates = {**updates, "towers": jax.tree.map(
                lambda u: u * participation.reshape(
                    (u.shape[0],) + (1,) * (u.ndim - 1)).astype(u.dtype),
                updates["towers"])}
        params = apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), metrics

    return local_step, apply_step


def build_eval_step(model: Model, num_clients: int) -> Callable:
    """eval_step(params, batch) -> per-task metrics (paper Eq. 14 accuracy)."""
    cfg = model.cfg
    M = num_clients
    is_classifier = cfg.family in ("mlp", "resnet")

    def _chunk_eval(params, batch, c):
        n = M // c

        def blk(tree):
            return jax.tree.map(
                lambda x: x.reshape((n, c) + x.shape[1:]), tree)

        xs = {"towers": blk(params["towers"]), "batch": blk(batch)}
        server = params["server"]

        def body(carry, x):
            inputs = {k: v for k, v in x["batch"].items() if k != "label"}
            smashed = jax.vmap(model.tower_forward)(x["towers"], inputs)
            flat = jax.tree.map(
                lambda t: t.reshape((-1,) + t.shape[2:]), smashed)
            logits, _ = model.server_forward(server, flat)
            logits = logits.astype(jnp.float32)
            if is_classifier:
                preds = jnp.argmax(logits, -1).reshape(c, -1)
                correct = (preds == x["batch"]["label"]).astype(jnp.float32)
                return carry, jnp.mean(correct, axis=1)
            return carry, jax.vmap(_lm_loss)(
                logits.reshape((c, -1) + logits.shape[1:]),
                x["batch"]["tokens"])

        _, per = jax.lax.scan(body, None, xs)
        per = per.reshape(M)
        if is_classifier:
            return {"per_task_acc": per, "acc_mtl": jnp.mean(per)}
        return {"per_task_loss": per, "loss": jnp.sum(per)}

    def eval_step(params, batch):
        chunk = client_axis.current_chunk()
        if chunk is not None and chunk < M and M % chunk == 0:
            return _chunk_eval(params, batch, chunk)
        inputs = {k: v for k, v in batch.items() if k != "label"}
        smashed = jax.vmap(model.tower_forward)(params["towers"], inputs)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), smashed)
        logits, _ = model.server_forward(params["server"], flat)
        logits = logits.astype(jnp.float32)
        if is_classifier:
            preds = jnp.argmax(logits, -1).reshape(M, -1)
            correct = (preds == batch["label"]).astype(jnp.float32)
            per_task_acc = jnp.mean(correct, axis=1)  # [M]
            return {"per_task_acc": per_task_acc, "acc_mtl": jnp.mean(per_task_acc)}
        per = jax.vmap(_lm_loss)(
            logits.reshape((M, -1) + logits.shape[1:]), batch["tokens"]
        )
        return {"per_task_loss": per, "loss": jnp.sum(per)}

    return eval_step
