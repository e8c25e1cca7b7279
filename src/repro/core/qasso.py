"""QASSO: Quantization-Aware Structured Sparse Optimizer (paper §5, Alg 2-4).

Solves   min f(x, d, q_m, t)
         s.t. Card{g in G : [x]_g = 0} = K          (Eq 7b)
              b_i in [b_l, b_u]  for i in L          (Eq 7c)

through four stages driven by the step counter (all jit-compatible; the
stage switch is a lax.switch and period boundaries are lax.cond):

  warm-up     [0, K_w)                      : base optimizer on everything.
  projection  [K_w, K_w + B*K_b)            : PPSG (Alg 3) — SGD on
              (d, q_m, t), then project *only d* into the [d_min, d_max]
              implied by the progressively-shrinking range [b_l, b_u - p*b_r].
  joint       [.., + P*K_p)                 : saliency partition G_I / G_R
              per period; G_I gets the base step (Eq 8); G_R additionally
              forgets the *quantized* value -gamma*[x_Q]_g (Eq 9) with the
              angle-based gamma (Eq 16) / d (Eq 17) rules, kept feasible by
              the adaptive Alg 4 rescaling; (t, q_m) get SGD (line 14).
  cool-down   [.., total)                   : redundant groups hard-zeroed,
              (d*, q_m*, t*) frozen, G_I trains to convergence (line 22).

Deviations from the paper are documented inline and in DESIGN.md §2.2:
- alpha*||grad|| in Eqs 16/17 uses the scheduled lr and the raw gradient
  (the theory assumes SGD; we allow Adam-family base optimizers).
- Redundant partitions are sticky across periods (monotone pruning), the
  standard OTO-family behaviour.
- Non-quantized params (norm scales, biases) in redundant groups forget
  their raw value at the uniform case-1 rate (x_Q := x when no quantizer).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.core.groups import GroupFamily, Member, PruningSpace, _axis_mask, \
    _broadcast_to_axis
from repro.core.qadg import QuantSite
from repro.core.saliency import SaliencyConfig, global_redundancy_partition
from repro.optim.base import Optimizer, get_optimizer, tree_add

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class QASSOConfig:
    # Eq 7b / 7c targets
    target_sparsity: float = 0.5          # K, fraction of prunable units
    bit_lower: float = 4.0                # b_l
    bit_upper: float = 16.0               # b_u (initial, before reduction)
    # Alg 2 schedule
    warmup_steps: int = 100               # K_w
    projection_periods: int = 5           # B
    projection_steps: int = 50            # K_b
    bit_reduction: float = 2.0            # b_r
    pruning_periods: int = 5              # P
    pruning_steps: int = 50               # K_p
    cooldown_steps: int = 200
    # Eq 16/17 + Alg 4 constants (paper Appendix B)
    eta: float = 0.9
    xi: float = 0.999
    eps: float = 1e-8
    beta: float = 0.5
    # lrs
    lr_quant: float = 1e-4                # Appendix C: constant for (d,q_m,t)
    base_optimizer: str = "adamw"
    grad_clip: float = 0.0
    saliency: SaliencyConfig = dataclasses.field(default_factory=SaliencyConfig)

    # -- derived boundaries --
    @property
    def warmup_end(self) -> int:
        return self.warmup_steps

    @property
    def projection_end(self) -> int:
        return self.warmup_steps + self.projection_periods * self.projection_steps

    @property
    def joint_end(self) -> int:
        return self.projection_end + self.pruning_periods * self.pruning_steps

    @property
    def total_steps(self) -> int:
        return self.joint_end + self.cooldown_steps

    @property
    def bit_upper_final(self) -> float:
        return max(self.bit_upper - self.bit_reduction * self.projection_periods,
                   self.bit_lower)


class QASSOState(NamedTuple):
    step: jax.Array
    base: Any                      # base optimizer state over x
    redundant: dict[str, jax.Array]   # per-family: 1.0 = in G_R this period
    keep_mask: dict[str, jax.Array]   # per-family: 1.0 = kept (hard, set at joint end)
    gamma: jax.Array               # (num_weight_sites,) last forget rates


class QASSO:
    """Usage (mirrors the paper's Framework Usage box):

        qasso = QASSO(qadg.space, qadg.sites, cfg, lr_schedule)
        state = qasso.init(params, qparams)
        ...
        (loss, (gx, gq)) = value_and_grad(f, (0, 1))(params, qparams, batch)
        params, qparams, state, metrics = qasso.update(
            params, qparams, gx, gq, state)
    """

    def __init__(self, space: PruningSpace, sites: list[QuantSite],
                 cfg: QASSOConfig,
                 lr_schedule: Callable[[jax.Array], jax.Array]):
        self.space = space
        self.sites = list(sites)
        self.weight_sites = [s for s in sites if s.kind == "weight"]
        self.act_sites = [s for s in sites if s.kind == "act"]
        self.cfg = cfg
        self.lr_schedule = lr_schedule
        self.base: Optimizer = get_optimizer(cfg.base_optimizer)
        self.mesh = None
        self._stat_reduce = lambda x: x
        # param -> [(family, member)] covering map (prunable families only)
        self.covering: dict[str, list[tuple[GroupFamily, Member]]] = {}
        for fam in space.prunable_families():
            for m in fam.members:
                self.covering.setdefault(m.param, []).append((fam, m))
        self.total_units = space.total_units()
        self.k_units = int(round(cfg.target_sparsity * self.total_units))
        self.site_of_param = {p: s.name for s in self.weight_sites
                              for p in s.quantized_params}

    # ----------------------------------------------------------- sharding
    def replica_consistent(self, mesh) -> "QASSO":
        """Pin the optimizer's control statistics to `mesh`-replicated
        layouts (DESIGN.md §5): the seven Eq 15-17 reductions per weight
        site and the saliency accumulators get an explicit cross-replica
        all-reduce (`collectives.replicate_stats`) before any decision —
        partition ranking, bit-width projection, cooldown hard-zeroing —
        consumes them. Without this GSPMD may combine partial sums at
        replica-dependent points and the replicas drift onto different
        subnets. Call before tracing the sharded train step."""
        from repro.distributed.collectives import replicate_stats
        self.mesh = mesh
        self._stat_reduce = replicate_stats(mesh)
        return self

    # ------------------------------------------------------------------ init
    def init(self, params: dict, qparams: dict) -> QASSOState:
        del qparams
        masks = {f.name: jnp.zeros((f.units,), jnp.float32)
                 for f in self.space.prunable_families()}
        keep = {f.name: jnp.ones((f.units,), jnp.float32)
                for f in self.space.prunable_families()}
        return QASSOState(
            step=jnp.zeros((), jnp.int32),
            base=self.base.init(params),
            redundant=masks,
            keep_mask=keep,
            gamma=jnp.zeros((max(len(self.weight_sites), 1),), jnp.float32),
        )

    # ------------------------------------------------------- mask utilities
    def _elem_mask(self, pname: str, unit_masks: dict[str, jax.Array],
                   arr: jax.Array) -> jax.Array:
        """Elementwise mask for `pname`: max over covering families (an
        element is flagged if ANY covering unit is flagged)."""
        m = None
        for fam, mem in self.covering.get(pname, []):
            am = _axis_mask(unit_masks[fam.name], mem, arr.shape[mem.axis])
            bm = _broadcast_to_axis(am, arr.ndim, mem.axis)
            m = bm if m is None else jnp.maximum(m, jnp.broadcast_to(
                bm, m.shape))
            m = jnp.broadcast_to(m, arr.shape)
        if m is None:
            return jnp.zeros(arr.shape, jnp.float32)
        return m.astype(jnp.float32)

    def _mask_tree(self, params: dict, unit_masks: dict[str, jax.Array]
                   ) -> dict[str, jax.Array]:
        return {p: self._elem_mask(p, unit_masks, arr)
                for p, arr in params.items()}

    def _keep_elem_tree(self, params: dict, keep_units: dict[str, jax.Array]
                        ) -> dict[str, jax.Array]:
        """Elementwise keep: an element survives iff ALL covering units are
        kept — i.e. 1 - (any covering unit pruned)."""
        pruned_units = {k: 1.0 - v for k, v in keep_units.items()}
        pruned_elem = self._mask_tree(params, pruned_units)
        return {p: 1.0 - m for p, m in pruned_elem.items()}

    # ---------------------------------------------------------- stage bodies
    def _quant_sgd(self, qparams: dict, grads_q: dict) -> dict:
        """Plain SGD with the constant quant lr, positivity-guarded."""
        lr = self.cfg.lr_quant
        out = {}
        for name, qp in qparams.items():
            gq = grads_q[name]
            out[name] = Q.positivity_guard(Q.QuantParams(
                d=qp.d - lr * gq.d, q_m=qp.q_m - lr * gq.q_m,
                t=qp.t - lr * gq.t))
        return out

    def _project_all(self, qparams: dict, b_u_eff: jax.Array) -> dict:
        return {name: Q.project_step_size(qp, self.cfg.bit_lower, b_u_eff)
                for name, qp in qparams.items()}

    # Eq 16 / Eq 17 / Alg 4, one weight site ------------------------------
    @staticmethod
    def _site_stats_chunked(w, g, r, d0, qm, t):
        """The seven masked reductions of Eqs 15-17 for one weight tensor.

        A flat formulation leaves ~5 simultaneous f32 copies of every weight
        alive (the `pow` in clip/residual is expensive, so XLA materializes
        the shared subexpressions feeding multiple reductions — measured
        ~200 GB/device on the 398B configs). For stacked (n_blocks, ...)
        tensors we scan block-by-block along the *unsharded* leading axis
        (a reshape(-1) would all-gather sharded axes), scoping temps to one
        block. No AD flows through optimizer statistics, so the scan costs
        nothing in the backward.

        The clipped magnitudes here use `jnp.power`, not the quantizers'
        exp·log form: with the latter, the 1-device and 4-device CPU runs of
        tests/test_sharded_training.py part ways at the first stage
        boundary, where these stats feed knife-edge decisions."""

        def stats_of(ws, gs, rs):
            ws = ws.astype(jnp.float32)
            gs = gs.astype(jnp.float32)
            sign = jnp.sign(ws)
            clipv = sign * Q.clip_qmt(jnp.abs(ws), qm, t, jnp.power)
            resv = sign * Q.residual(jnp.abs(ws), d0, qm, t, jnp.power)
            return jnp.stack([
                jnp.sum(rs * gs * clipv),
                jnp.sum(rs * gs * resv),
                jnp.sum(rs * jnp.square(gs)),
                jnp.sum(rs * jnp.square(clipv)),
                jnp.sum(rs * jnp.square(resv)),
                jnp.sum(rs * jnp.abs(clipv)),
                jnp.sum(rs),
            ])

        if w.ndim < 3 or w.shape[0] == 1:
            return stats_of(w, g, r)

        def body(acc, inp):
            ws, gs, rs = inp
            return acc + stats_of(ws, gs, rs), None

        acc, _ = jax.lax.scan(body, jnp.zeros((7,), jnp.float32), (w, g, r))
        return acc

    def _joint_site(self, site: QuantSite, params: dict, grads: dict,
                    qparams: dict, red_elem: dict, alpha: jax.Array,
                    k_in_period: jax.Array):
        cfg = self.cfg
        qp = qparams[site.name]
        d0, qm, t = qp.d, qp.q_m, qp.t

        # gather redundant-restricted statistics over the site's weights.
        # Under a mesh, `_stat_reduce` pins each INPUT to the replicated
        # layout first: the reductions then run locally over full tensors
        # in a mesh-size-invariant order, so every replica — and the
        # 1-device reference — sees bit-identical stats (the downstream
        # cos-sign branches and the Alg 4 rescale loop are knife edges).
        stats = jnp.zeros((7,), jnp.float32)
        for pname in site.quantized_params:
            stats = stats + self._site_stats_chunked(
                self._stat_reduce(params[pname]),
                self._stat_reduce(grads[pname]),
                self._stat_reduce(red_elem[pname]), d0, qm, t)
        dot_clip, dot_res, n_g2, n_clip2, n_res2, clip_sum, cnt = stats

        n_g = jnp.sqrt(n_g2)
        n_clip = jnp.sqrt(n_clip2)
        n_res = jnp.sqrt(n_res2)
        clip_mean = clip_sum / jnp.maximum(cnt, 1.0)
        # angle between -g and -sgn*clip equals angle between g and sgn*clip
        cos_g = dot_clip / jnp.maximum(n_g * n_clip, _EPS)
        cos_d = dot_res / jnp.maximum(n_g * n_res, _EPS)

        has_red = cnt > 0.5
        case0 = jnp.logical_and(has_red, clip_mean <= cfg.eps)

        # Eq 16
        k_left = jnp.maximum(cfg.pruning_steps - k_in_period, 1.0)
        gamma_uniform = 1.0 / k_left          # 1 - (Kp-k-1)/(Kp-k)
        gamma_neg = -(1.0 - cfg.eta) * alpha * n_g / (
            cos_g * jnp.maximum(n_clip, _EPS))
        gamma = jnp.where(case0, 0.0,
                          jnp.where(cos_g >= 0, gamma_uniform, gamma_neg))
        gamma = jnp.where(has_red, gamma, 0.0)

        # Eq 17
        d_low = Q.step_size_for_bits(qm, t, jnp.float32(cfg.bit_lower))
        d_neg = -(cfg.xi * cfg.eta * alpha * n_g) / (
            jnp.maximum(gamma, _EPS) * cos_d * jnp.maximum(n_res, _EPS))
        d_new = jnp.where(cos_d >= 0, d_low, d_neg)
        # sites with nothing redundant keep their step size (projected)
        d_new = jnp.where(jnp.logical_and(has_red, gamma > 0), d_new, d0)

        # Alg 4: rescale (gamma, d) until b in [b_l, b_u_final]
        b_l = jnp.float32(cfg.bit_lower)
        b_u = jnp.float32(cfg.bit_upper_final)

        def bits(d):
            return Q.bit_width(d, qm, t)

        def cond(carry):
            g_, d_, it = carry
            b = bits(d_)
            return jnp.logical_and(
                jnp.logical_or(b > b_u + 1e-6, b < b_l - 1e-6), it < 200)

        def body(carry):
            g_, d_, it = carry
            b = bits(d_)
            too_high = b > b_u  # too many bits -> d too small
            g2 = jnp.where(too_high, cfg.beta * g_, g_)
            d2 = jnp.where(too_high, d_ / cfg.beta, cfg.beta * d_)
            return g2, d2, it + 1

        gamma, d_new, _ = jax.lax.while_loop(
            cond, body, (gamma, jnp.maximum(d_new, 1e-8),
                         jnp.zeros((), jnp.int32)))
        return gamma, d_new, case0

    # ------------------------------------------------------------- stages
    def _stage_warmup(self, params, qparams, gx, gq, state, lr, delta, base2):
        new_params = tree_add(params, delta)
        new_q = self._quant_sgd(qparams, gq)
        return new_params, new_q, state.redundant, state.keep_mask, state.gamma

    def _stage_projection(self, params, qparams, gx, gq, state, lr, delta,
                          base2):
        cfg = self.cfg
        new_params = tree_add(params, delta)
        # Alg 3 line 2: SGD on (d, q_m, t)
        new_q = self._quant_sgd(qparams, gq)
        # progressive range: period p reduces the upper bound by p*b_r
        period = (state.step - cfg.warmup_end) // jnp.maximum(
            cfg.projection_steps, 1)
        b_u_eff = jnp.maximum(
            jnp.float32(cfg.bit_upper) - cfg.bit_reduction
            * (period.astype(jnp.float32) + 1.0),
            jnp.float32(cfg.bit_lower))
        # Alg 3 lines 3-4: project only d
        new_q = self._project_all(new_q, b_u_eff)
        return new_params, new_q, state.redundant, state.keep_mask, state.gamma

    def _stage_joint(self, params, qparams, gx, gq, state, lr, delta, base2):
        cfg = self.cfg
        step = state.step
        joint_start = cfg.projection_end
        k_in_period = ((step - joint_start) % jnp.maximum(cfg.pruning_steps, 1)
                       ).astype(jnp.float32)
        period = (step - joint_start) // jnp.maximum(cfg.pruning_steps, 1)
        is_boundary = (step - joint_start) % jnp.maximum(
            cfg.pruning_steps, 1) == 0

        # Alg 2 lines 11-12: recompute the partition at period start,
        # progressive target round(K * (p+1)/P), sticky across periods.
        n_red = jnp.round(
            self.k_units * (period.astype(jnp.float32) + 1.0)
            / max(cfg.pruning_periods, 1)).astype(jnp.int32)

        def recompute(_):
            # sticky: previously redundant units are pinned (-inf score) so
            # they remain in G_R and count toward the progressive target.
            return global_redundancy_partition(
                self.space, params, gx, n_red, cfg.saliency,
                pinned=state.redundant, reduce=self._stat_reduce)

        redundant = jax.lax.cond(is_boundary, recompute,
                                 lambda _: state.redundant, None)

        red_elem = self._mask_tree(params, redundant)
        alpha = lr

        # line 14: (t, q_m) one SGD step (d handled by Eq 17 below)
        q_sgd = self._quant_sgd(qparams, gq)
        new_q = {}
        gammas = []
        site_gamma_for_param: dict[str, tuple[jax.Array, jax.Array]] = {}
        wsite_names = {s.name for s in self.weight_sites}
        for site in self.weight_sites:
            qp_s = Q.QuantParams(d=qparams[site.name].d,
                                 q_m=q_sgd[site.name].q_m,
                                 t=q_sgd[site.name].t)
            tmp_q = dict(qparams)
            tmp_q[site.name] = qp_s
            gamma, d_new, case0 = self._joint_site(
                site, params, gx, tmp_q, red_elem, alpha, k_in_period)
            new_q[site.name] = Q.positivity_guard(
                Q.QuantParams(d=d_new, q_m=qp_s.q_m, t=qp_s.t))
            gammas.append(gamma)
            for pname in site.quantized_params:
                site_gamma_for_param[pname] = (gamma, case0)
        # act sites: SGD + keep feasible (PPSG on the final range)
        for site in self.act_sites:
            new_q[site.name] = Q.project_step_size(
                q_sgd[site.name], cfg.bit_lower, cfg.bit_upper_final)
        for name in qparams:
            if name not in new_q:
                new_q[name] = q_sgd[name]

        # Eq 8 / Eq 9
        k_left = jnp.maximum(cfg.pruning_steps - k_in_period, 1.0)
        gamma_plain = 1.0 / k_left
        new_params = {}
        for pname, w in params.items():
            dlt = delta[pname]
            r = red_elem[pname]
            if pname in site_gamma_for_param:
                gamma, case0 = site_gamma_for_param[pname]
                # x_Q with the *new* step size (Alg 2 line 18)
                sname = self.site_of_param[pname]
                qp_n = new_q[sname]
                xq = Q.fake_quant(w, qp_n.d, qp_n.q_m, qp_n.t).astype(
                    jnp.float32)
                forget = gamma * xq
                upd = w + dlt - (r * forget).astype(w.dtype)
                upd = jnp.where(jnp.logical_and(case0, r > 0.5),
                                jnp.zeros_like(upd), upd)
            else:
                # non-quantized param: forget the raw value (x_Q := x)
                upd = w + dlt - (r * gamma_plain * w).astype(w.dtype)
            new_params[pname] = upd

        # joint end: hard-zero G_R, freeze keep mask (entering cool-down)
        is_last = step == (cfg.joint_end - 1)

        def finalize(args):
            prms, keep = args
            keep2 = {k: 1.0 - redundant[k] for k in keep}
            elem_keep = self._keep_elem_tree(prms, keep2)
            prms2 = {p: a * elem_keep[p].astype(a.dtype)
                     for p, a in prms.items()}
            return prms2, keep2

        new_params, keep_mask = jax.lax.cond(
            is_last, finalize, lambda a: a, (new_params, state.keep_mask))

        gamma_vec = (jnp.stack(gammas) if gammas
                     else jnp.zeros((1,), jnp.float32))
        return new_params, new_q, redundant, keep_mask, gamma_vec

    def _stage_cooldown(self, params, qparams, gx, gq, state, lr, delta,
                        base2):
        # line 22: fixed (d*, q_m*, t*); only G_I trains; G_R pinned at 0.
        keep_elem = self._keep_elem_tree(params, state.keep_mask)
        new_params = {p: (params[p] + delta[p]) * keep_elem[p].astype(
            params[p].dtype) for p in params}
        return new_params, qparams, state.redundant, state.keep_mask, \
            state.gamma

    # -------------------------------------------------------------- update
    def stage_index(self, step: jax.Array) -> jax.Array:
        cfg = self.cfg
        return (jnp.asarray(step >= cfg.warmup_end, jnp.int32)
                + jnp.asarray(step >= cfg.projection_end, jnp.int32)
                + jnp.asarray(step >= cfg.joint_end, jnp.int32))

    def update(self, params: dict, qparams: dict, gx: dict, gq: dict,
               state: QASSOState):
        cfg = self.cfg
        lr = self.lr_schedule(state.step)
        if cfg.grad_clip > 0:
            from repro.optim.base import clip_by_global_norm
            gx, _ = clip_by_global_norm(gx, cfg.grad_clip)

        # During cool-down, pruned units must not pollute base-opt moments.
        keep_elem = self._keep_elem_tree(params, state.keep_mask)
        gx_eff = {p: gx[p] * keep_elem[p].astype(gx[p].dtype) for p in gx}
        delta, base2 = self.base.update(gx_eff, state.base, params, lr)

        stage = self.stage_index(state.step)
        branches = [self._stage_warmup, self._stage_projection,
                    self._stage_joint, self._stage_cooldown]
        new_params, new_q, redundant, keep_mask, gamma = jax.lax.switch(
            stage, [lambda a, b=b: b(*a) for b in branches],
            (params, qparams, gx, gq, state, lr, delta, base2))

        new_state = QASSOState(step=state.step + 1, base=base2,
                               redundant=redundant, keep_mask=keep_mask,
                               gamma=gamma)
        bits = jnp.stack([Q.bit_width(new_q[s.name].d, new_q[s.name].q_m,
                                      new_q[s.name].t)
                          for s in self.sites]) if self.sites else \
            jnp.zeros((1,))
        metrics = {
            "stage": stage,
            "lr": lr,
            "sparsity_hard": self.space.sparsity(keep_mask),
            "sparsity_partition": self.space.sparsity(
                {k: 1.0 - v for k, v in redundant.items()}),
            "bits_mean": jnp.mean(bits),
            "bits_min": jnp.min(bits),
            "bits_max": jnp.max(bits),
            "gamma_mean": jnp.mean(gamma),
        }
        return new_params, new_q, new_state, metrics
