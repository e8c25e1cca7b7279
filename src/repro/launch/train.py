"""Training drivers: step builders (shared with the dry-run) + a real
CPU-scale end-to-end loop with QASSO, checkpointing and fault tolerance.

Usage (reduced scale, runs on this container):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (SHAPES, CompressionConfig, get_arch,
                           get_overrides)
from repro.core.qadg import build_qadg
from repro.core.qasso import QASSO, QASSOConfig, QASSOState
from repro.data.synthetic import batch_for
from repro.distributed.fault import FaultConfig, FaultTolerantLoop
from repro.distributed.sharding import batch_spec, make_plan
from repro.models.transformer import LM
from repro.optim.base import AdamState, get_optimizer, tree_add
from repro.optim.schedules import constant, cosine


def qasso_config_from(comp: CompressionConfig,
                      base_optimizer: str = "adamw") -> QASSOConfig:
    return QASSOConfig(
        target_sparsity=comp.target_sparsity,
        bit_lower=comp.bit_lower, bit_upper=comp.bit_upper,
        warmup_steps=comp.warmup_steps,
        projection_periods=comp.projection_periods,
        projection_steps=comp.projection_steps,
        bit_reduction=comp.bit_reduction,
        pruning_periods=comp.pruning_periods,
        pruning_steps=comp.pruning_steps,
        cooldown_steps=comp.cooldown_steps,
        base_optimizer=base_optimizer)


def build_geta(lm: LM, comp: CompressionConfig, lr: float,
               base_optimizer: str = "adamw"):
    """(qadg, qasso) for a model — the paper's `geta = GETA(model)`."""
    qadg = build_qadg(lm.build_graph(act_quant=comp.act_quant).graph)
    qcfg = qasso_config_from(comp, base_optimizer)
    qasso = QASSO(qadg.space, qadg.sites, qcfg,
                  cosine(lr, qcfg.total_steps, warmup=qcfg.warmup_steps))
    return qadg, qasso


def _constrain_tree(tree, shardings):
    if shardings is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x, s: x if s is None else jax.lax.with_sharding_constraint(x, s),
        tree, shardings)


def _accumulate_grads(loss_grad_fn, batch, microbatches: int,
                      grad_example, mb_sharding=None, grad_shardings=None):
    """Scan-accumulated gradients over `microbatches` splits of the global
    batch (f32 accumulators — per-device activation memory scales with
    1/microbatches at fixed global batch).

    loss_grad_fn(microbatch) -> (loss, grads_pytree).
    mb_sharding: optional NamedSharding for the reshaped (k, B/k, ...)
    batch — without the explicit constraint GSPMD can drop the batch
    sharding across the reshape (measured 3.5x temp regression).
    grad_shardings: optional tree of NamedShardings matching grad_example;
    pins the f32 accumulators (scan carries) to the parameter shardings —
    GSPMD's carry fixed-point otherwise all-gathers FSDP-sharded expert
    grads (measured ~35 full f32 copies on jamba-398b).
    Returns (mean loss, mean grads)."""
    def split(x):
        y = x.reshape(microbatches, x.shape[0] // microbatches,
                      *x.shape[1:])
        if mb_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = P(None, *mb_sharding.spec)
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mb_sharding.mesh, spec))
        return y

    mbatch = jax.tree_util.tree_map(split, batch)
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), grad_example)
    zeros = _constrain_tree(zeros, grad_shardings)

    def body(acc, mb):
        loss_acc, g_acc = acc
        loss, grads = loss_grad_fn(mb)
        g_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
        g_acc = _constrain_tree(g_acc, grad_shardings)
        return (loss_acc + loss, g_acc), None

    (loss_sum, g_sum), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros),
                                        mbatch)
    scale = 1.0 / microbatches
    return loss_sum * scale, jax.tree_util.tree_map(
        lambda g: g * scale, g_sum)


def make_geta_train_step(lm: LM, qasso: QASSO, microbatches: int = 1,
                         mb_sharding=None, grad_shardings=None):
    """The production train step: loss -> grads -> QASSO joint update."""

    def step(params, qparams, qstate, batch):
        def lg(b):
            loss, grads = jax.value_and_grad(lm.loss, argnums=(0, 1))(
                params, qparams, b)
            return loss, grads

        if microbatches <= 1:
            loss, (gx, gq) = lg(batch)
        else:
            loss, (gx, gq) = _accumulate_grads(lg, batch, microbatches,
                                               (params, qparams),
                                               mb_sharding=mb_sharding,
                                               grad_shardings=grad_shardings)
        params, qparams, qstate, metrics = qasso.update(
            params, qparams, gx, gq, qstate)
        metrics["loss"] = loss
        return params, qparams, qstate, metrics

    return step


# ------------------------------------------------------- sharded training
def geta_state_shardings(qasso: QASSO, params, qparams, mesh,
                         param_shardings=None):
    """Plan-derived shardings for the full GETA state tree.

    params follow the ShardingPlan (FSDP shards the embed axis over the DP
    axes when the plan says so); the base-optimizer moments follow their
    parameters (they are elementwise companions, so FSDP sharding of the
    params shards the optimizer state for free); everything control-plane —
    quantizer scalars, redundancy/keep masks, step counter, gamma — is
    replicated (they are the values QASSO must agree on across replicas).
    Returns (param_sh, qparam_sh, qstate_sh) pytrees of NamedShardings.
    """
    rep = NamedSharding(mesh, P())
    p_sh = {k: (param_shardings or {}).get(k) or rep for k in params}
    q_sh = jax.tree_util.tree_map(lambda _: rep, qparams)
    state_shape = jax.eval_shape(qasso.init, params, qparams)
    base = state_shape.base
    if isinstance(base, AdamState):
        base_sh = AdamState(rep, {k: p_sh[k] for k in base.m},
                            {k: p_sh[k] for k in base.v})
    elif isinstance(base, dict):                 # momentum: one moment tree
        base_sh = {k: p_sh[k] for k in base}
    else:                                        # sgd: stateless
        base_sh = jax.tree_util.tree_map(lambda _: rep, base)
    s_sh = QASSOState(
        step=rep, base=base_sh,
        redundant={k: rep for k in state_shape.redundant},
        keep_mask={k: rep for k in state_shape.keep_mask},
        gamma=rep)
    return p_sh, q_sh, s_sh


def _gather_full(x, spec, axis_name_filter=None):
    """Reassemble a shard_map-local param shard to the full tensor.

    `spec` is the param's PartitionSpec; every sharded dim is all-gathered
    (tiled) in minor-to-major axis order, which reconstructs the original
    array bitwise (pure data movement, no arithmetic)."""
    for dim, part in enumerate(spec):
        if part is None:
            continue
        names = (part,) if isinstance(part, str) else tuple(part)
        for name in reversed(names):
            x = jax.lax.all_gather(x, name, axis=dim, tiled=True)
    return x


def make_ordered_loss_grads(lm, mesh, param_specs_tree=None,
                            grad_slices: Optional[int] = None,
                            axis: str = "data"):
    """(loss, (gx, gq)) with a DETERMINISTIC reduction tree over the batch.

    The global batch is split into `grad_slices` equal slices (default: the
    mesh's `axis` size); each slice's gradients are computed independently
    and combined by f32 summation in FIXED slice order. Two properties fall
    out:

    - k-device data parallelism is **bitwise-reproducible across mesh
      sizes**: the k-device run (one slice per device via shard_map,
      all-gather + ordered sum) produces bit-identical loss and gradients
      to a 1-device run of the same step with `grad_slices=k` (sequential
      unrolled accumulation — same tree, same order). This is what lets
      the sharded-parity tier assert exact equality instead of chasing
      reduction-order ulps through QASSO's discrete decisions (saliency
      ranking, fake-quant rounding, the Alg 4 rescale loop), every one of
      which is a knife edge that amplifies a 1-ulp gradient difference
      into a diverged subnet.
    - the combine is an all-gather + local ordered sum rather than a psum
      (the `compressed_grad_allreduce` wire pattern): k× gradient bytes on
      the wire vs 2(k-1)/k for a ring — the documented cost of determinism
      (DESIGN.md §5). The scalar loss is pinned with an optimization
      barrier on the sequential path: XLA otherwise duplicates the cheap
      loss reduction into differently-fused consumers and reassociates the
      metric by a few ulps (state is unaffected — only the metric).

    FSDP params are handled inside the shard_map body: sharded params are
    all-gathered (tiled, bitwise) to full before the slice computation, so
    gradients are identical whether params live replicated or sharded.
    """
    dp = dict(mesh.shape).get(axis, 1)
    k = grad_slices or max(dp, 1)
    if dp > 1 and k != dp:
        raise ValueError(
            f"deterministic grads need one slice per device: "
            f"grad_slices={k} but mesh has {dp} '{axis}' devices")

    def lg_fn(p, q, bb):
        # trace WITHOUT the model's internal sharding constraints: the
        # k-device body runs under shard_map (constraints are illegal on
        # manual axes) and the 1-device reference must lower the exact
        # same computation (a constraint-induced fusion difference breaks
        # the bitwise contract). Restored right after the trace so the
        # caller's lm is untouched.
        if hasattr(lm, "act_sharding"):
            saved = (lm.act_sharding, lm.param_shardings)
            lm.act_sharding = None
            lm.param_shardings = None
            try:
                return jax.value_and_grad(lm.loss, argnums=(0, 1))(p, q, bb)
            finally:
                lm.act_sharding, lm.param_shardings = saved
        return jax.value_and_grad(lm.loss, argnums=(0, 1))(p, q, bb)

    scale = jnp.float32(1.0 / k)

    if dp == 1:
        # sequential reference: unrolled slice loop, ordered f32 accumulate
        def lg(params, qparams, batch):
            slices = jax.tree_util.tree_map(
                lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]),
                batch)
            ls, g_acc = [], None
            for i in range(k):
                mb = jax.tree_util.tree_map(lambda x: x[i], slices)
                l, g = lg_fn(params, qparams, mb)
                ls.append(l.astype(jnp.float32))
                gf = jax.tree_util.tree_map(
                    lambda t: t.astype(jnp.float32), g)
                g_acc = gf if g_acc is None else jax.tree_util.tree_map(
                    jnp.add, g_acc, gf)
            lsa = jax.lax.optimization_barrier(jnp.stack(ls))
            loss = lsa[0]
            for i in range(1, k):
                loss = loss + lsa[i]
            return loss * scale, jax.tree_util.tree_map(
                lambda t: t * scale, g_acc)

        return lg

    from jax.sharding import PartitionSpec

    def body(params, qparams, batch):
        if param_specs_tree is not None:
            params = {name: _gather_full(w, param_specs_tree[name])
                      for name, w in params.items()}
        loss, (gx, gq) = lg_fn(params, qparams, batch)

        def combine(x):
            xs = jax.lax.all_gather(x.astype(jnp.float32), axis)  # (k, ...)
            acc = xs[0]
            for i in range(1, k):
                acc = acc + xs[i]
            return acc * scale

        return combine(loss), (jax.tree_util.tree_map(combine, gx),
                               jax.tree_util.tree_map(combine, gq))

    p_specs = (dict(param_specs_tree) if param_specs_tree is not None
               else PartitionSpec())
    lg = jax.shard_map(body, mesh=mesh,
                       in_specs=(p_specs, PartitionSpec(),
                                 PartitionSpec(axis)),
                       out_specs=(PartitionSpec(),
                                  (PartitionSpec(), PartitionSpec())),
                       check_vma=False)
    return lg


def make_sharded_geta_train_step(lm, qasso: QASSO, mesh, params, qparams, *,
                                 param_shardings=None,
                                 grad_slices: Optional[int] = None,
                                 deterministic: bool = True,
                                 microbatches: int = 1):
    """The GETA step jitted against a real device mesh.

    - in/out shardings are derived from the ShardingPlan via
      `geta_state_shardings` (data-parallel batch over the mesh's DP axes,
      params/opt-state per plan — replicated for pure DP, sharded for FSDP);
    - gradients come from `make_ordered_loss_grads` when deterministic
      (the default): bitwise-reproducible across mesh sizes, so a k-device
      run exactly matches the 1-device reference with `grad_slices=k`.
      `deterministic=False` falls back to plain GSPMD value_and_grad
      (ring psum, cheaper wire, ulp-level reduction-order noise);
    - QASSO runs replica-consistent (`qasso.replica_consistent(mesh)`):
      the saliency and Eq 15-17 statistics are computed from explicitly
      replicated inputs, so partition ranking, bit-width projections and
      cooldown hard-zeroing are identical on every device — and identical
      to the 1-device run, since full-tensor reductions then happen
      locally in a mesh-size-invariant order;
    - the step traces under `dispatch.on_mesh(mesh)`: a mesh of more than
      one device routes GEMMs to the partitionable XLA path, a one-device
      mesh keeps the platform's kernels.

    Returns (jitted_step, (param_sh, qparam_sh, qstate_sh, batch_sh)).
    Callers `jax.device_put` the initial state and each batch with the
    returned shardings; `batch_sh` is a pytree-prefix sharding valid for
    any batch dict.
    """
    import copy

    from repro.kernels.dispatch import on_mesh

    # the step closes over a COPY so the caller's qasso keeps working in
    # non-mesh contexts (replica_consistent pins stat layouts to `mesh`,
    # which would poison a later plain-jit trace of the same object)
    qasso = copy.copy(qasso).replica_consistent(mesh)
    p_sh, q_sh, s_sh = geta_state_shardings(qasso, params, qparams, mesh,
                                            param_shardings)
    batch_sh = NamedSharding(mesh, batch_spec(mesh))
    rep = NamedSharding(mesh, P())

    if deterministic:
        if microbatches > 1:
            raise ValueError(
                "microbatches>1 is only supported with deterministic="
                "False (the deterministic path computes one gradient per "
                "batch slice; use grad_slices to control the split)")
        specs_tree = ({k: v.spec for k, v in param_shardings.items()}
                      if param_shardings else None)
        lg = make_ordered_loss_grads(lm, mesh, specs_tree,
                                     grad_slices=grad_slices)

        def step(params, qparams, qstate, batch):
            with on_mesh(mesh):
                loss, (gx, gq) = lg(params, qparams, batch)
                params, qparams, qstate, metrics = qasso.update(
                    params, qparams, gx, gq, qstate)
            metrics["loss"] = loss
            return params, qparams, qstate, metrics
    else:
        base_step = make_geta_train_step(
            lm, qasso, microbatches=microbatches,
            mb_sharding=batch_sh if microbatches > 1 else None,
            grad_shardings=(p_sh, q_sh) if microbatches > 1 else None)

        def step(params, qparams, qstate, batch):
            with on_mesh(mesh):
                return base_step(params, qparams, qstate, batch)

    jstep = jax.jit(step,
                    in_shardings=(p_sh, q_sh, s_sh, batch_sh),
                    out_shardings=(p_sh, q_sh, s_sh, rep))
    return jstep, (p_sh, q_sh, s_sh, batch_sh)


def make_base_train_step(lm: LM, optimizer_name: str = "adamw",
                         lr: float = 3e-4):
    """Vanilla (no-GETA) train step — the roofline comparison baseline."""
    opt = get_optimizer(optimizer_name)
    sched = constant(lr)

    def step(params, opt_state, step_idx, batch):
        loss, gx = jax.value_and_grad(
            lambda p: lm.loss(p, None, batch))(params)
        delta, opt_state = opt.update(gx, opt_state, params,
                                      sched(step_idx))
        params = tree_add(params, delta)
        return params, opt_state, step_idx + 1, loss

    return step, opt


# ---------------------------------------------------------------- driver
def train_loop(arch: str, smoke: bool, steps: int, batch: int, seq: int,
               ckpt_dir: Optional[str] = None, seed: int = 0,
               comp: Optional[CompressionConfig] = None,
               inject_failure_at: Optional[int] = None,
               log_every: int = 10, verbose: bool = True,
               mesh=None, fsdp: bool = False,
               checkpoint_every: Optional[int] = None):
    """GETA training driver. `mesh=None` is the single-device path; passing
    a mesh jits the step with ShardingPlan-derived in/out shardings
    (data-parallel batch, FSDP params when fsdp=True) and checkpoints place
    restored leaves with the CURRENT mesh's shardings (elastic resume).

    The checkpoint carries the FULL state tree — params, qparams, the whole
    QASSOState (base-optimizer moments, step counter, partition masks) and
    the data-RNG key — so a killed run resumes on a bitwise-identical
    trajectory (tests/test_checkpoint_resume.py)."""
    cfg = get_arch(arch, smoke=smoke)
    comp = comp or CompressionConfig(
        warmup_steps=max(steps // 10, 2),
        projection_periods=2, projection_steps=max(steps // 10, 2),
        pruning_periods=3, pruning_steps=max(steps // 10, 2),
        cooldown_steps=max(steps // 4, 2))
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(seed))
    qparams = lm.init_qparams(params, bits_init=16.0,
                              act_quant=comp.act_quant)
    base_opt = get_overrides(arch).get("base_optimizer", "adamw")
    qadg, qasso = build_geta(lm, comp, lr=3e-4, base_optimizer=base_opt)
    qadg.space.validate(params)

    batch_sh = None
    state_sh = None
    if mesh is not None:
        from repro.launch.specs import param_specs
        plan = make_plan(mesh, fsdp=fsdp,
                         overrides=dict(get_overrides(arch)))
        _, p_sh, _ = param_specs(lm, mesh, plan)
        jstep, (p_sh, q_sh, s_sh, batch_sh) = make_sharded_geta_train_step(
            lm, qasso, mesh, params, qparams, param_shardings=p_sh)
        params = jax.device_put(params, p_sh)
        qparams = jax.device_put(qparams, q_sh)
        qstate = jax.device_put(qasso.init(params, qparams), s_sh)
        rep = NamedSharding(mesh, P())
        state_sh = {"params": p_sh, "qparams": q_sh, "qstate": s_sh,
                    "rng": rep}
    else:
        qstate = qasso.init(params, qparams)
        jstep = jax.jit(make_geta_train_step(lm, qasso))

    from repro.checkpoint import restore_checkpoint, save_checkpoint

    # state["rng"] holds the data key for the NEXT step (equal to
    # fold_in(PRNGKey(seed), step), so the stream is identical to the
    # stateless form); checkpointing it means a restored run consumes the
    # exact saved key rather than re-deriving it — the RNG stream is part
    # of the bitwise-replay contract.
    rng0 = jax.random.PRNGKey(seed)
    state = {"params": params, "qparams": qparams, "qstate": qstate,
             "rng": jax.random.fold_in(rng0, 0)}
    losses = []
    pending_failure = [inject_failure_at]   # one-shot injection

    def step_fn(state, i):
        if pending_failure[0] is not None and i == pending_failure[0]:
            pending_failure[0] = None
            raise RuntimeError("injected node failure")
        b = batch_for(cfg, seed, i, batch, seq, key=state["rng"])
        if batch_sh is not None:
            b = jax.device_put(b, batch_sh)
        p, q, s, metrics = jstep(state["params"], state["qparams"],
                                 state["qstate"], b)
        losses.append(float(metrics["loss"]))
        if verbose and i % log_every == 0:
            print(f"step {i:4d} stage={int(metrics['stage'])} "
                  f"loss={float(metrics['loss']):.4f} "
                  f"bits=[{float(metrics['bits_min']):.1f},"
                  f"{float(metrics['bits_max']):.1f}] "
                  f"sparsity={float(metrics['sparsity_hard']):.3f}")
        return {"params": p, "qparams": q, "qstate": s,
                "rng": jax.random.fold_in(rng0, i + 1)}

    if ckpt_dir:
        def save_fn(state, i):
            save_checkpoint(ckpt_dir, i, state)

        def restore_fn():
            return restore_checkpoint(ckpt_dir, state, shardings=state_sh)

        loop = FaultTolerantLoop(
            FaultConfig(checkpoint_every=checkpoint_every
                        or max(steps // 4, 1)),
            step_fn, save_fn, restore_fn)
        state, result = loop.run(state, steps)
        if verbose:
            print(f"done: {result}")
    else:
        for i in range(steps):
            state = step_fn(state, i)
    return state, qadg, qasso, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel mesh over the first N local devices "
                         "(CPU runs: JAX_PLATFORMS=cpu forces N host "
                         "devices)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params/opt-state over the data axis")
    args = ap.parse_args()
    from repro.launch import compile_cache
    from repro.launch.mesh import force_host_devices
    force_host_devices(args.devices or 0)
    compile_cache.enable()
    mesh = None
    if args.devices:
        from repro.launch.mesh import make_subset_mesh
        mesh = make_subset_mesh(args.devices)
    t0 = time.time()
    state, qadg, qasso, losses = train_loop(
        args.arch, args.smoke, args.steps, args.batch, args.seq,
        ckpt_dir=args.ckpt_dir, seed=args.seed, mesh=mesh, fsdp=args.fsdp)
    print(f"trained {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    sp = float(qasso.space.sparsity(state["qstate"].keep_mask))
    print(f"final hard sparsity: {sp:.3f}")


if __name__ == "__main__":
    main()
