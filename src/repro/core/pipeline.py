"""RoCoIn offline setup phase end-to-end (Fig. 1 left half).

1. Train the teacher on the (synthetic-CIFAR) task.
2. Record execution profiles; pass a validation set through the teacher and
   build the filter-activation graph of its final conv layer.
3. Run the knowledge-assignment planner against a heterogeneous fleet.
4. Distill one student per knowledge partition (Eq. 6) and train the
   aggregation FC head over concatenated student portions.

Returns an Ensemble ready for the runtime phase (quorum aggregation with
failure masking).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import activation_graph as AG
from repro.core import distill as DS
from repro.core import failout as FO
from repro.core import planner as PL
from repro.core.assignment import StudentArch
from repro.core.grouping import Device
from repro.data.images import ImageTaskConfig, SyntheticImages
from repro.models import cnn


# ---------------------------------------------------------------------------
# simple SGD-momentum trainer for CNNs
# ---------------------------------------------------------------------------

def sgd_init(params):
    return jax.tree.map(jnp.zeros_like, params)


def sgd_update(params, grads, mom, lr=0.05, momentum=0.9, wd=5e-4):
    def upd(p, g, m):
        if p.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
            return p, m
        g = g + wd * p
        m = momentum * m + g
        return p - lr * m, m
    out = jax.tree.map(upd, params, grads, mom)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    return new_p, new_m


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def merge_bn_stats(params, newp):
    """Carry ONLY the BatchNorm running statistics from the forward pass —
    every other leaf keeps its (SGD-updated) value."""
    def pick(path, p, n):
        key = jax.tree_util.keystr(path)
        return n if (key.endswith("['mean']") or key.endswith("['var']")) else p
    return jax.tree_util.tree_map_with_path(pick, params, newp)


def train_teacher(key, teacher_cfg: cnn.WRNConfig, data: SyntheticImages,
                  steps: int = 200, batch: int = 128, lr: float = 0.05
                  ) -> Tuple[Any, Dict]:
    params = cnn.wrn_init(key, teacher_cfg)
    mom = sgd_init(params)

    @jax.jit
    def step(params, mom, x, y):
        def loss_fn(p):
            logits, _, newp = cnn.wrn_forward(p, teacher_cfg, x, train=True)
            return _xent(logits, y), newp
        (loss, newp), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, mom = sgd_update(params, grads, mom, lr=lr)
        params = merge_bn_stats(params, newp)   # BN running stats only
        return params, mom, loss

    losses = []
    for i, (x, y) in enumerate(data.epoch(batch, steps)):
        params, mom, loss = step(params, mom, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
    return params, {"losses": losses}


def evaluate(forward, params, cfg, data: SyntheticImages, batches: int = 5,
             batch: int = 256, seed0: int = 10_000) -> float:
    correct = total = 0
    for i in range(batches):
        x, y = data.batch(batch, seed0 + i)
        logits, _, _ = forward(params, cfg, jnp.asarray(x))
        correct += int((np.asarray(logits).argmax(-1) == y).sum())
        total += len(y)
    return correct / total


# ---------------------------------------------------------------------------
# profiling the student zoo → StudentArch entries (Eq. 5 inputs)
# ---------------------------------------------------------------------------

def profile_student(name: str, n_classes: int, final_channels: int,
                    example: np.ndarray) -> StudentArch:
    cfg, params, forward = cnn.make_student(jax.random.key(0), name, n_classes,
                                            final_channels)
    compiled = jax.jit(
        lambda p, x: forward(p, cfg, x)[0]).lower(
            jax.eval_shape(lambda: params), jax.ShapeDtypeStruct(
                example.shape, jnp.float32)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    n_params = cnn.count_params(params)
    return StudentArch(name=f"{name}-f{final_channels}", flops=flops,
                       params=4.0 * n_params, out_bytes=4.0 * final_channels,
                       capacity=float(n_params))


# ---------------------------------------------------------------------------
# full offline pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ensemble:
    plan: PL.Plan
    students: List[Tuple[Any, Any, Callable]]   # (cfg, params, forward) per partition
    fc: Dict[str, jnp.ndarray]
    part_dims: List[int]
    teacher_acc: float
    ir: Optional["PlanIR"] = None               # canonical array-backed plan

    def fused_export(self):
        """Stacked-student export for the serving fast path, or None.

        Students are stackable when they share ONE arch family: identical
        configs and identical weight-pytree structure/shapes (the planner
        emits that whenever every partition gets the same zoo entry at the
        same width — uniform ``part_dims``). The export is a
        :class:`repro.runtime.serving.FusedStudents`: per-slot weight
        pytrees plus the single shared forward, which the server stacks
        along a leading K axis and vmaps over in one compiled megastep.
        Heterogeneous zoos fall back to the per-slot loop (returns None)."""
        from repro.runtime.serving import FusedStudents
        if len(self.students) < 2:
            return None
        cfg0, params0, fwd0 = self.students[0]
        shapes0 = [(l.shape, l.dtype)
                   for l in jax.tree_util.tree_leaves(params0)]
        td0 = jax.tree_util.tree_structure(params0)
        for cfg, params, _ in self.students[1:]:
            if cfg != cfg0:
                return None
            if jax.tree_util.tree_structure(params) != td0:
                return None
            if [(l.shape, l.dtype)
                    for l in jax.tree_util.tree_leaves(params)] != shapes0:
                return None

        def apply(params, x):
            _, feats, _ = fwd0(params, cfg0, x)
            return feats

        return FusedStudents(apply=apply,
                             params=[p for _, p, _ in self.students])

    def portions(self, x: jnp.ndarray, arrived: Optional[np.ndarray] = None
                 ) -> jnp.ndarray:
        outs = []
        for k, (cfg, params, forward) in enumerate(self.students):
            if arrived is not None and not arrived[k]:
                outs.append(None)
            else:
                _, feats, _ = forward(params, cfg, x)
                outs.append(feats)
        # batch hint keeps the beyond-quorum all-missing pattern defined
        # (zero features → FC bias) instead of raising mid-sweep
        return DS.aggregate_portions(outs, self.part_dims,
                                     batch=int(x.shape[0]))

    def predict(self, x: jnp.ndarray, arrived: Optional[np.ndarray] = None
                ) -> jnp.ndarray:
        return DS.fc_head_apply(self.fc, self.portions(x, arrived))

    def accuracy(self, data: SyntheticImages, arrived=None, batches: int = 4,
                 batch: int = 256, seed0: int = 10_000) -> float:
        correct = total = 0
        for i in range(batches):
            x, y = data.batch(batch, seed0 + i)
            pred = np.asarray(self.predict(jnp.asarray(x), arrived)).argmax(-1)
            correct += int((pred == y).sum())
            total += len(y)
        return correct / total

    def robustness_curve(self, data: SyntheticImages, *, max_losses: int = 2,
                         batches: int = 2, batch: int = 256,
                         seed0: int = 10_000) -> "FO.RobustnessCurve":
        """Measured accuracy-vs-#slot-losses export (every ≤max_losses
        pattern) — the contract :func:`repro.core.planner.thin_replicas`
        consumes to trade replicas against trained-in robustness."""
        return FO.measure_robustness_curve(
            lambda m: self.accuracy(data, arrived=m, batches=batches,
                                    batch=batch, seed0=seed0),
            len(self.students), max_losses)


@dataclasses.dataclass
class TeacherBundle:
    """A trained teacher + its activation graph (shareable across planner
    variants — the offline phase's expensive part)."""
    cfg: cnn.WRNConfig
    params: Any
    acc: float
    A: np.ndarray
    data: SyntheticImages


def prepare_teacher(key, *, n_classes: int = 10, teacher_depth: int = 16,
                    teacher_widen: int = 4, teacher_steps: int = 150,
                    batch: int = 128,
                    data: Optional[SyntheticImages] = None) -> TeacherBundle:
    data = data or SyntheticImages(ImageTaskConfig(n_classes=n_classes))
    tcfg = cnn.WRNConfig(f"wrn-{teacher_depth}-{teacher_widen}", teacher_depth,
                         teacher_widen, n_classes)
    tparams, _ = train_teacher(key, tcfg, data, steps=teacher_steps, batch=batch)
    teacher_acc = evaluate(cnn.wrn_forward, tparams, tcfg, data)
    xs, _ = data.batch(256, 77_000)
    _, tfeats, _ = cnn.wrn_forward(tparams, tcfg, jnp.asarray(xs))
    acts = AG.average_activity(tfeats)
    A = np.asarray(AG.activation_graph(acts))
    return TeacherBundle(tcfg, tparams, teacher_acc, A, data)


def build_rocoin(key, *, n_classes: int = 10, teacher_depth: int = 16,
                 teacher_widen: int = 4, devices: Optional[Sequence[Device]] = None,
                 d_th: Optional[float] = None, p_th: float = 0.25,
                 teacher_steps: int = 150, student_steps: int = 150,
                 zoo: Optional[List[str]] = None,
                 data: Optional[SyntheticImages] = None,
                 planner: str = "rocoin",
                 teacher: Optional[TeacherBundle] = None,
                 failout: Optional[FO.FailoutConfig] = None,
                 batch: int = 128) -> Ensemble:
    """Run the whole offline phase. planner ∈ {rocoin, rocoin-g, hetnonn, nonn}.

    ``failout`` appends the failure-aware phase: after per-student
    distillation and FC training, students + head are jointly fine-tuned on
    the quorum-merged prediction under sampled aliveness masks
    (:func:`failout_finetune`) so the ensemble degrades gracefully under
    every trained ≤r-loss pattern."""
    from repro.core import simulator as SIM

    devices = list(devices) if devices is not None else SIM.make_fleet(8, seed=1)
    zoo = zoo or (cnn.STUDENT_ZOO_C10 if n_classes <= 10 else cnn.STUDENT_ZOO_C100)

    k_t, k_s, k_fc = jax.random.split(key, 3)
    if teacher is None:
        teacher = prepare_teacher(k_t, n_classes=n_classes,
                                  teacher_depth=teacher_depth,
                                  teacher_widen=teacher_widen,
                                  teacher_steps=teacher_steps, batch=batch,
                                  data=data)
    data = teacher.data
    tcfg, tparams, teacher_acc, A = (teacher.cfg, teacher.params,
                                     teacher.acc, teacher.A)
    xs, _ = data.batch(256, 77_000)

    # student zoo profiled at a nominal final width (re-profiled per plan below)
    M = A.shape[0]
    example = xs[:1]

    def zoo_for(final_ch: int) -> List[StudentArch]:
        return [profile_student(n, n_classes, final_ch, example) for n in zoo]

    nominal = zoo_for(max(M // max(len(devices) // 2, 1), 8))

    ir = None
    if planner == "rocoin":
        # the canonical IR is the planner's native output; the legacy Plan
        # below is a derived view for the distillation loop
        ir = (PL.make_plan_ir(devices, A, nominal, d_th=d_th, p_th=p_th)
              if d_th is not None else
              PL.tune_d_th_ir(devices, A, nominal, p_th=p_th))
        plan = ir.to_plan(devices=devices, students=nominal)
    elif planner == "rocoin-g":
        plan = PL.plan_rocoin_g(devices, A, nominal, d_th=d_th or 1.0, p_th=p_th)
    elif planner == "hetnonn":
        plan = PL.plan_hetnonn(devices, A, nominal, p_th=p_th)
    elif planner == "nonn":
        plan = PL.plan_nonn(devices, A, nominal, p_th=p_th)
    else:
        raise KeyError(planner)

    # distill one student per partition
    students, part_dims = [], []
    plan.groups.sort(key=lambda g: g.partition_idx)
    skeys = jax.random.split(k_s, max(plan.K, 1))
    for slot, g in enumerate(plan.groups):
        part = np.asarray(g.filters, np.int64)
        dim = max(len(part), 1)
        part_dims.append(dim)
        sname = (g.student.name.rsplit("-f", 1)[0] if g.student else zoo[-1])
        scfg, sparams, sfwd = cnn.make_student(skeys[slot], sname, n_classes, dim)
        sparams = _distill_student(sparams, scfg, sfwd, tparams, tcfg, part,
                                   data, steps=student_steps, batch=batch)
        students.append((scfg, sparams, sfwd))

    # train the FC aggregation head on concatenated portions
    fc = DS.fc_head_init(k_fc, sum(part_dims), n_classes)
    fc = _train_fc(fc, students, part_dims, data,
                   steps=max(student_steps // 2, 10), batch=batch)
    if ir is None:      # baseline planners produce object plans; lift them
        from repro.core.plan_ir import PlanIR
        ir = PlanIR.from_plan(plan, students=nominal, devices=devices)
    ens = Ensemble(plan, students, fc, part_dims, teacher_acc, ir=ir)
    if failout is not None:
        ens = failout_finetune(ens, teacher, failout, batch=batch)
    return ens


def failout_finetune(ens: Ensemble, teacher: TeacherBundle,
                     cfg: FO.FailoutConfig, *, steps: Optional[int] = None,
                     batch: int = 128, lr: float = 0.01,
                     dcfg: DS.DistillConfig = DS.DistillConfig()) -> Ensemble:
    """Failout phase: jointly fine-tune every student AND the FC head on the
    quorum-merged prediction under sampled aliveness masks.

    Per step, the concatenated student portions are computed ONCE and the
    merged KD loss is vmapped over the leading pattern axis
    (:func:`repro.core.distill.failout_merged_loss`) — one compiled step
    regardless of P. Masks come from the config's
    :class:`~repro.core.failout.FailoutSampler` (pattern enumeration or the
    vectorized failure simulator), split per-step from a deterministic
    ``(seed, step)`` stream; the all-alive pattern is always pattern 0, so
    the failure-free path stays in the objective and does not regress.
    ``FailoutConfig(max_losses=0)`` runs the identical loop on the all-alive
    pattern only — the equal-compute failure-blind baseline. ``lr`` is
    fine-tune-scale (well below the distillation lr) so neither arm walks
    away from the base ensemble it refines.

    Returns a NEW :class:`Ensemble` (the input is not mutated — benchmarks
    branch failout and failure-blind arms off one base ensemble)."""
    from repro.core import simulator as SIM
    steps = cfg.steps if steps is None else steps
    arrays = None
    if cfg.mode == "scenario":
        arrays = SIM.plan_arrays(ens.ir if ens.ir is not None else ens.plan)
    sampler = FO.FailoutSampler(cfg, n_slots=len(ens.students), arrays=arrays)
    weights = sampler.weights()
    data = teacher.data
    tparams, tcfg = teacher.params, teacher.cfg

    cfgs = [c for c, _, _ in ens.students]
    fwds = [f for _, _, f in ens.students]
    plist = [p for _, p, _ in ens.students]
    moms = [sgd_init(p) for p in plist]
    fc, fcm = ens.fc, jax.tree.map(jnp.zeros_like, ens.fc)

    @jax.jit
    def step(plist, fc, moms, fcm, x, y, col_masks):
        t_logits, _, _ = cnn.wrn_forward(tparams, tcfg, x)

        def loss_fn(ps, f):
            feats, newps = [], []
            for scfg, sfwd, p in zip(cfgs, fwds, ps):
                _, fk, newp = sfwd(p, scfg, x, train=True)
                feats.append(fk)
                newps.append(newp)
            cat = jnp.concatenate(feats, axis=-1)
            loss = DS.failout_merged_loss(f, cat, t_logits, y, col_masks,
                                          jnp.asarray(weights), dcfg)
            return loss, newps

        (loss, newps), (gp, gf) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(plist, fc)
        out_p, out_m = [], []
        for p, g, m, newp in zip(plist, gp, moms, newps):
            p2, m2 = sgd_update(p, g, m, lr=lr)
            out_p.append(merge_bn_stats(p2, newp))   # BN running stats only
            out_m.append(m2)
        fc2, fcm2 = sgd_update(fc, gf, fcm, lr=2 * lr, wd=0.0)
        return out_p, fc2, out_m, fcm2, loss

    for i, (x, y) in enumerate(data.epoch(batch, steps, seed0=130_000)):
        col_masks = DS.expand_slot_masks(sampler.masks(i), ens.part_dims)
        plist, fc, moms, fcm, _ = step(plist, fc, moms, fcm,
                                       jnp.asarray(x), jnp.asarray(y),
                                       col_masks)
    students = [(c, p, f) for (c, _, f), p in zip(ens.students, plist)]
    return dataclasses.replace(ens, students=students, fc=fc)


@functools.partial(jax.jit, static_argnames=("scfg", "sfwd", "tcfg", "dcfg"))
def _distill_step(sparams, mom, tparams, part, x, y, *, scfg, sfwd, tcfg,
                  dcfg):
    # teacher weights and the partition are arguments, not closed-over
    # constants: students of one arch and width share one compiled step
    t_logits, t_feats, _ = cnn.wrn_forward(tparams, tcfg, x)
    t_part = t_feats[:, part]

    def loss_fn(p):
        logits, feats, newp = sfwd(p, scfg, x, train=True)
        return DS.distill_loss(logits, feats, t_logits, t_part, y, dcfg), newp

    (loss, newp), grads = jax.value_and_grad(loss_fn, has_aux=True)(sparams)
    sparams2, mom2 = sgd_update(sparams, grads, mom)
    sparams2 = merge_bn_stats(sparams2, newp)   # BN running stats only
    return sparams2, mom2, loss


def _distill_student(sparams, scfg, sfwd, tparams, tcfg, part, data,
                     steps=150, batch=128, dcfg: DS.DistillConfig = DS.DistillConfig()):
    mom = sgd_init(sparams)
    part = jnp.asarray(part)
    for i, (x, y) in enumerate(data.epoch(batch, steps, seed0=50_000)):
        sparams, mom, _ = _distill_step(sparams, mom, tparams, part,
                                        jnp.asarray(x), jnp.asarray(y),
                                        scfg=scfg, sfwd=sfwd, tcfg=tcfg,
                                        dcfg=dcfg)
    return sparams


def _train_fc(fc, students, part_dims, data, steps=80, batch=128):
    m = jax.tree.map(jnp.zeros_like, fc)

    def portions(x):
        outs = []
        for cfg, params, fwd in students:
            _, feats, _ = fwd(params, cfg, x)
            outs.append(feats)
        return jnp.concatenate(outs, axis=-1)

    @jax.jit
    def step(fc, m, x, y):
        feats = portions(x)

        def loss_fn(f):
            return _xent(DS.fc_head_apply(f, feats), y)

        loss, grads = jax.value_and_grad(loss_fn)(fc)
        fc2, m2 = sgd_update(fc, grads, m, lr=0.1, wd=0.0)
        return fc2, m2, loss

    for i, (x, y) in enumerate(data.epoch(batch, steps, seed0=90_000)):
        fc, m, _ = step(fc, m, jnp.asarray(x), jnp.asarray(y))
    return fc
