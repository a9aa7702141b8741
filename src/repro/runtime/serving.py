"""Fault-tolerant quorum serving runtime (RoCoIn Fig. 1, runtime phase).

The source node:
  1. batches incoming requests,
  2. broadcasts the input to every live replica worker,
  3. collects portions; a partition is satisfied by its FIRST arriving
     replica (replication masks crashes/timeouts),
  4. starts the FC merge as soon as one replica of every partition arrived
     (quorum) OR the deadline expires — late/missing portions are zeroed
     (degraded mode, the paper's §V behaviour),
  5. straggler mitigation: requests are *hedged* — all replicas of a group
     compute in parallel by design, so a straggler only hurts if ALL its
     group's members straggle,
  6. elastic: on permanent device loss the planner re-plans and students are
     re-deployed (weights already distilled; only placement changes).

Latency accounting uses the paper's Eq. 1a device model; the actual portion
math runs as real JAX computation, and the merge uses the fused Pallas
quorum_aggregate kernel.

Hot path — the fused fast path: when the ensemble's students share an arch
family their weights are exported as ONE stacked pytree (leading K axis,
feature dims padded once at build/migrate time, see :class:`FusedStudents`)
and :meth:`QuorumServer.serve_batch` dispatches a single jitted megastep
that vmaps the portion forward over the student axis, applies the arrived
mask device-side, and flows straight into the fused quorum_aggregate merge
— one dispatch per micro-batch, zero host round-trips between forward and
merge, and the result stays on device (:class:`ServeResult` defers the
host sync until ``.logits`` is read, so the engine can overlap the next
micro-batch). ``quantize="int8"`` switches to weight-only int8 deployment:
stacked student weights and FC slices are stored int8 with per-slot fp32
scales and dequantized inside the compiled program (the merge consumes the
int8 W_k in-kernel) — ~4x less HBM weight traffic for memory-bound edge
portions.

The legacy one-forward-per-partition loop stays behind ``fastpath=False``
as the reference oracle: the fp32 fast path is bit-identical to it at
fixed seeds (the merge is linear in each portion, and padding only appends
exact-zero columns).

Coded plans (a PlanIR carrying a :class:`repro.coding.spec.CodingSpec`)
serve through the same two paths. While every systematic share arrives the
flow is IDENTICAL to uncoded serving (the code is systematic — zero
overhead, bit-exact). When a systematic share is erased but its group
holds ≥ k of its n shares, the parity shares are emulated inside the
compiled program (one einsum against the stacked generator parity rows —
the central stand-in for the parity devices' coded networks, as in the
paper's §V emulation), host-built pseudo-inverse decode weights recover
the missing portions via the fused :func:`repro.kernels.coded_decode
.coded_decode` kernel, and the result flows into the same quorum merge.
The fused megastep folds forward → encode → decode → merge into ONE
dispatch; the legacy loop runs the identical math through the jitted ops
wrappers and remains the bit-identical oracle.

Compute-coded plans (a PlanIR carrying a
:class:`repro.coding.compute.ComputeCodingSpec`) split a slot's output
matmul column-wise into k weight shards plus r parity shards — pre-encoded
at deploy time, each 1/k of the slot's work — and the serve path completes
the slot from the FIRST k shard arrivals (cancel-on-first-k). When those k
are exactly the systematic shards the flow is a plain passthrough
(bit-exact with uncoded serving); otherwise host-built pseudo-inverse
weights recover the k data blocks via the same fused coded_decode kernel.
Per-request shard arrival times are exposed on
:attr:`ServeResult.share_times` so the continuous-batching engine can
track fan-out futures and count cancelled in-flight shares.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.grouping import Device
from repro.core.plan_ir import PlanIR
from repro.core.planner import Plan
from repro.core.simulator import (FailureModel, plan_arrays, reduce_trials,
                                  reduce_trials_coded)
from repro.kernels import coded_decode as _cd
from repro.kernels import ops as K
from repro.kernels import quorum_aggregate as _qa
from repro.optim.compression import (Int8Weights, dequantize_tree,
                                     quantize_tree, quantize_weight)


@dataclasses.dataclass
class ServeResult:
    """One request's answer. ``logits`` is lazy: the device array backing
    the whole micro-batch is held until first access, so callers that only
    look at quorum metadata (the serving engine) never force a host sync —
    and ``failed_devices`` is derived on demand from the aliveness row (it
    is only read by chaos tests)."""
    latency: float
    arrived: np.ndarray           # (K,) bool
    degraded: bool
    # coded plans only: per-share arrival times (R_sh,), ∞ = never — the
    # continuous-batching engine turns these into per-share future events
    # on its virtual clock (cancel-on-first-k speculation accounting)
    share_times: Optional[np.ndarray] = None
    _logits: Any = dataclasses.field(default=None, repr=False)
    _span: Optional[Tuple[int, int]] = dataclasses.field(
        default=None, repr=False)
    _alive: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    _names: Optional[Sequence[str]] = dataclasses.field(
        default=None, repr=False)
    _np_logits: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    @property
    def logits(self) -> np.ndarray:
        """This request's merged logits (B, C), materialized lazily from
        the shared micro-batch buffer."""
        if self._np_logits is None:
            x = self._logits
            if self._span is not None:
                x = x[self._span[0]:self._span[1]]
            self._np_logits = np.asarray(x)
            self._logits = None    # release the shared micro-batch buffer
        return self._np_logits

    @property
    def coverage(self) -> float:
        """Fraction of partitions recovered (arrived directly or decoded
        from coded shares) — mirrors ``TrialResult.coverage``. 1.0 for a
        complete answer; a degraded answer had ``1 - coverage`` of its
        portions zeroed at the merge."""
        return float(self.arrived.mean()) if len(self.arrived) else 0.0

    @property
    def failed_devices(self) -> List[str]:
        """Names of the devices that were down for this request."""
        if self._alive is None:
            return []
        return [self._names[j] for j in np.flatnonzero(~self._alive)]

    def block_until_ready(self) -> "ServeResult":
        """Wait for the device computation backing ``logits`` (shared by the
        whole micro-batch). The engine calls this inside its timed region in
        measured-wall mode so service times stay honest."""
        if self._logits is not None:
            jax.block_until_ready(self._logits)
        return self


@dataclasses.dataclass
class FusedStudents:
    """The stacked-student export behind the fused fast path.

    ``apply(slot_params, x) -> (B, Dk)`` is ONE portion forward shared by
    every slot (students share an arch family); ``params`` holds each
    slot's UNPADDED weight pytree, and ``pad(slot_params, Dk)`` pads a
    slot's feature dims to the uniform width (identity when ``None``).
    Padding happens once at build/migrate time — the serve path sees a
    single pytree with a leading K axis and vmaps ``apply`` over it.

    ``pre(x)``, when set, is a slot-INDEPENDENT prefix (e.g. a shared
    trunk) computed once per batch outside the vmap — its output feeds
    ``apply`` as the second argument, so K-invariant compute is hoisted by
    construction instead of relying on XLA CSE across the vmapped body."""
    apply: Callable[[Any, jnp.ndarray], jnp.ndarray]
    params: List[Any]
    pad: Optional[Callable[[Any, int], Any]] = None
    pre: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None

    def padded(self, k: int, width: int) -> Any:
        """Slot ``k``'s params padded to the uniform feature ``width``."""
        p = self.params[k]
        return self.pad(p, width) if self.pad is not None else p


def _stack_trees(trees: Sequence[Any]) -> Any:
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def _is_int8(leaf) -> bool:
    return isinstance(leaf, Int8Weights)


def _set_stacked_row(stacked: Any, k: int, row: Any) -> Any:
    """Write one slot's (possibly int8-quantized) padded pytree into row
    ``k`` of the stacked pytree — the single definition both migrate and
    deploy_slot use, so the int8 row-update semantics cannot diverge."""
    def put(leaf, new_leaf):
        if _is_int8(leaf):
            return Int8Weights(leaf.q.at[k].set(new_leaf.q),
                               leaf.scale.at[k].set(new_leaf.scale))
        return leaf.at[k].set(new_leaf)
    return jax.tree.map(put, stacked, row, is_leaf=_is_int8)


@dataclasses.dataclass
class QuorumServer:
    """Quorum-of-portions inference server over a (possibly coded) plan.

    Runs every placed student portion, masks the ones whose devices failed,
    decodes coded shares when needed, and merges with the fused
    ``quorum_aggregate`` kernel. Live-migratable via :meth:`migrate`.
    """

    plan: Any                     # planner.Plan or the canonical PlanIR
    portion_fns: List[Callable[[jnp.ndarray], jnp.ndarray]]  # per partition
    fc_weights: jnp.ndarray       # (K, Dk, C) padded per-partition FC slices
    fc_bias: jnp.ndarray          # (C,)
    deadline: float = float("inf")
    failure: Any = dataclasses.field(default_factory=FailureModel)
    rng: np.random.Generator = dataclasses.field(
        default_factory=lambda: np.random.default_rng(0))
    part_dims: Optional[Tuple[int, ...]] = None   # true per-slot feature dims
    # slots whose FC slice a migration zeroed (no stored weights for their
    # new partition): they contribute nothing to the merge, so results are
    # reported degraded until deploy_slot pushes real weights
    zeroed_slots: frozenset = frozenset()
    # content-addressed weight store: (new_ir, slot) -> (portion_fn, fc_slice)
    # or (portion_fn, fc_slice, slot_params) for the slot's partition, or
    # None when no weights exist for it. Used by :meth:`migrate` to rebuild
    # slots whose partition mask changed (slot_params feeds the fused path).
    redeploy_fn: Optional[Callable[[PlanIR, int], Optional[Tuple]]] = None
    # fused fast path: stacked-student export; None → legacy per-slot loop.
    fused: Optional[FusedStudents] = None
    # None = auto (fused whenever an export exists); False pins the legacy
    # per-slot loop (the reference oracle for equivalence tests)
    fastpath: Optional[bool] = None
    quantize: str = "none"        # none | int8 (weight-only deployment)
    _jitted: Optional[List[Optional[Callable]]] = dataclasses.field(
        default=None, init=False, repr=False)
    _jit_dk: int = dataclasses.field(default=-1, init=False, repr=False)
    _arrays: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False)
    _ir: Optional[PlanIR] = dataclasses.field(
        default=None, init=False, repr=False)
    _fused_stacked: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False)
    _fused_step: Optional[Callable] = dataclasses.field(
        default=None, init=False, repr=False)
    _fused_step_coded: Optional[Callable] = dataclasses.field(
        default=None, init=False, repr=False)
    _fused_step_compute: Optional[Callable] = dataclasses.field(
        default=None, init=False, repr=False)
    _mask_stack: Optional[Callable] = dataclasses.field(
        default=None, init=False, repr=False)
    # the per-slot loop's zeros portion of a slot no row received, by
    # (B, Dk): put on the device once per shape, never per batch
    _zero_portions: Dict = dataclasses.field(
        default_factory=dict, init=False, repr=False)
    _coded_rt: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False)
    _compute_rt: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False)
    _fc_q: Optional[Int8Weights] = dataclasses.field(
        default=None, init=False, repr=False)
    _det_cache: Dict = dataclasses.field(
        default_factory=dict, init=False, repr=False)
    last_migration: Optional[Dict] = dataclasses.field(
        default=None, init=False, repr=False)

    # optional obs plane (plain class attributes, not dataclass fields —
    # the owning engine wires them; timestamps come from ``tracer.now``,
    # the server holds no clock of its own)
    tracer = None
    trace_name = ""

    # -- compiled state ------------------------------------------------------

    @property
    def jitted_portions(self) -> List[Callable]:
        """Portion forwards for the legacy loop, jit'd once and reused for
        every request. Each wrapper pads its output to the uniform slice
        width INSIDE the compiled function, so padding costs one trace at
        construction/migration instead of a ``jnp.pad`` dispatch per
        request. Slots invalidated by a migration (None entries) re-jit
        lazily; untouched slots keep their compiled function. A change of
        the uniform width invalidates every wrapper."""
        Dk = int(self.fc_weights.shape[1])
        if self._jitted is None or self._jit_dk != Dk:
            self._jitted = [None] * len(self.portion_fns)
            self._jit_dk = Dk
        for i, fn in enumerate(self._jitted):
            if fn is None:
                self._jitted[i] = jax.jit(_padded_portion(
                    self.portion_fns[i], Dk, i))
        return self._jitted

    @property
    def ir(self) -> PlanIR:
        """Canonical array-backed view of the current plan."""
        if isinstance(self.plan, PlanIR):
            return self.plan
        if self._ir is None:
            self._ir = PlanIR.from_plan(self.plan)
        return self._ir

    @property
    def arrays(self):
        """Cached PlanArrays view of the plan (rebuilt after migrations)."""
        if self._arrays is None:
            self._arrays = plan_arrays(self.plan)
        return self._arrays

    @property
    def fastpath_active(self) -> bool:
        """True when serve_batch will take the single-dispatch fused path."""
        if self.fastpath is False:
            return False
        if self.fastpath and self.fused is None:
            raise ValueError("fastpath=True but the server has no stacked "
                             "student export (fused=None)")
        return self.fused is not None

    def _ensure_fused(self) -> Tuple[Any, Callable]:
        """Build (lazily) the stacked weight pytree — quantized to int8 when
        ``quantize='int8'`` — and the compiled megastep."""
        if self._fused_stacked is None:
            Dk = int(self.fc_weights.shape[1])
            padded = [self.fused.padded(k, Dk)
                      for k in range(len(self.fused.params))]
            stacked = _stack_trees(padded)
            if self.quantize == "int8":
                stacked = quantize_tree(stacked, axis=0)
            self._fused_stacked = stacked
        if self._fc_q is None and self.quantize == "int8":
            self._fc_q = quantize_weight(self.fc_weights, axis=0)
        if self._fused_step is None:
            self._fused_step = self._build_fused_step()
        return self._fused_stacked, self._fused_step

    def _build_fused_step(self) -> Callable:
        """ONE compiled program for the whole micro-batch: (optional int8
        dequant →) vmapped portion forward over the stacked K axis →
        device-side per-row arrived mask → fused quorum_aggregate merge.
        No host round-trip between forward and merge."""
        apply = self.fused.apply
        pre = self.fused.pre
        int8 = self.quantize == "int8"
        interpret = jax.default_backend() != "tpu"

        def step(stacked, x, row_mask, any_mask, fc_w, fc_scales, fc_b, *,
                 masked):
            params = dequantize_tree(stacked) if int8 else stacked
            if pre is not None:
                x = pre(x)                   # shared trunk: once, not K times
            portions = jax.vmap(apply, in_axes=(0, None))(params, x)
            if masked:
                # masks arrive as the sampler's raw numpy bools —
                # converting INSIDE the program keeps the host path free of
                # eager dispatches (an eager jnp.asarray costs ~100µs per
                # call). The all-arrived batch skips the multiply entirely
                # (static masked=False) — multiplying by 1.0 is bit-exact,
                # so both traces serve identical logits
                portions = portions * row_mask.T[:, :, None].astype(
                    portions.dtype)
            return _qa.quorum_aggregate(portions, fc_w, fc_b, any_mask,
                                        fc_scales, interpret=interpret)

        return jax.jit(step, static_argnames=("masked",))

    def _mask_stack_step(self) -> Callable:
        """The per-slot loop's one compiled program between the slot
        forwards and the merge kernel, built once: stack the K (B, Dk)
        portions and zero the rows of each slot that missed their
        request's quorum. The (B, K) row mask arrives as the sampler's
        numpy bools and is converted inside, so the loop issues no eager
        op; a clean batch passes its all-ones mask too (multiplying by 1.0
        is bit-exact), so each (B, K, Dk) compiles once."""
        if self._mask_stack is None:
            def mask_stack(portions, row_mask):
                stacked = jnp.stack(portions)             # (K, B, Dk)
                return stacked * row_mask.T[:, :, None].astype(
                    stacked.dtype)

            self._mask_stack = jax.jit(mask_stack)
        return self._mask_stack

    def _zero_portion(self, rows: int, width: int) -> jax.Array:
        """The (rows, width) float32 zeros on the device, memoized (a put,
        not an eager op)."""
        zeros = self._zero_portions.get((rows, width))
        if zeros is None:
            zeros = jax.device_put(np.zeros((rows, width), np.float32))
            self._zero_portions[(rows, width)] = zeros
        return zeros

    def _invalidate_fused(self) -> None:
        self._fused_stacked = None
        self._fused_step = None
        self._fused_step_coded = None
        self._fused_step_compute = None
        self._fc_q = None

    # -- coded-redundancy state ----------------------------------------------

    def _coded_runtime(self, ir):
        """The plan's coded-serving glue (encode matrix + memoized decode
        weights), rebuilt whenever a migration installs a new IR; None for
        replicate-only plans."""
        spec = getattr(ir, "coding", None)
        if spec is None or not spec.n_groups:
            return None
        rt = self._coded_rt
        if rt is None or rt.ir is not ir:
            from repro.coding.runtime import CodedRuntime
            rt = CodedRuntime(ir)
            self._coded_rt = rt
        return rt

    def _compute_runtime(self, ir):
        """The plan's compute-coding glue (per-slot generators + memoized
        first-k decode weights, see :class:`repro.coding.compute
        .ComputeRuntime`), rebuilt whenever a migration installs a new IR;
        None for plans without intermediate-computation coding."""
        spec = getattr(ir, "compute_coding", None)
        if spec is None or not spec.Q:
            return None
        rt = self._compute_rt
        if rt is None or rt.ir is not ir:
            from repro.coding.compute import ComputeRuntime
            rt = ComputeRuntime(ir)
            self._compute_rt = rt
            self._fused_step_compute = None   # closes over the runtime
        return rt

    def _coded_step(self) -> Callable:
        if self._fused_step_coded is None:
            self._fused_step_coded = self._build_fused_step_coded()
        return self._fused_step_coded

    def _compute_step(self) -> Callable:
        if self._fused_step_compute is None:
            self._fused_step_compute = self._build_fused_step_compute()
        return self._fused_step_compute

    def _build_fused_step_coded(self) -> Callable:
        """The coded twin of :meth:`_build_fused_step`: (optional int8
        dequant →) vmapped portion forward → parity-share encode (one
        einsum against the stacked generator parity rows) → fused masked
        pseudo-inverse decode → quorum merge, all in ONE compiled program.
        ``dec``/``share_mask`` arrive as the host-built numpy decode
        weights and share-arrival mask; nothing crosses back to the host
        between forward and merge."""
        apply = self.fused.apply
        pre = self.fused.pre
        int8 = self.quantize == "int8"
        interpret = jax.default_backend() != "tpu"

        def step(stacked, x, dec, share_mask, any_mask, enc, fc_w,
                 fc_scales, fc_b):
            params = dequantize_tree(stacked) if int8 else stacked
            if pre is not None:
                x = pre(x)                   # shared trunk: once, not K times
            portions = jax.vmap(apply, in_axes=(0, None))(params, x)
            parity = jnp.einsum("pk,kbf->pbf", enc, portions)
            shares = jnp.concatenate([portions, parity], axis=0)
            decoded = _cd.coded_decode(shares, dec, share_mask,
                                       interpret=interpret)
            return _qa.quorum_aggregate(decoded, fc_w, fc_b, any_mask,
                                        fc_scales, interpret=interpret)

        return jax.jit(step)

    def _build_fused_step_compute(self) -> Callable:
        """The compute-coded megastep: vmapped portion forward → per-coded
        -slot output-column sharding + parity encode (the central emulation
        of the shard devices' pre-encoded weights) → fused first-k decode
        via the :func:`repro.kernels.coded_decode.coded_decode` kernel →
        per-row arrived mask → quorum merge, ONE compiled program.
        ``decs``/``masks`` arrive as host-built per-request decode weights
        over each trial's k EARLIEST shard arrivals (the cancel-on-first-k
        semantics: later shards were cancelled and are never read)."""
        apply = self.fused.apply
        pre = self.fused.pre
        int8 = self.quantize == "int8"
        interpret = jax.default_backend() != "tpu"
        rtc = self._compute_runtime(self.ir)
        entries = [(e.slot, e.k, jnp.asarray(e.G[e.k:], jnp.float32))
                   for e in rtc.entries]
        decode = functools.partial(_cd.coded_decode, interpret=interpret)

        def step(stacked, x, decs, masks, row_mask, any_mask, fc_w,
                 fc_scales, fc_b):
            params = dequantize_tree(stacked) if int8 else stacked
            if pre is not None:
                x = pre(x)                   # shared trunk: once, not K times
            portions = jax.vmap(apply, in_axes=(0, None))(params, x)
            rec = {}
            for (slot, k, Gpar), dec, m in zip(entries, decs, masks):
                rec[slot] = _compute_decode(portions[slot], Gpar, k, dec, m,
                                            decode)
            portions = jnp.stack([rec.get(s, portions[s])
                                  for s in range(portions.shape[0])])
            portions = portions * row_mask.T[:, :, None].astype(portions.dtype)
            return _qa.quorum_aggregate(portions, fc_w, fc_b, any_mask,
                                        fc_scales, interpret=interpret)

        return jax.jit(step)

    # -- serving -------------------------------------------------------------

    def serve(self, x: jnp.ndarray, *,
              rng: Optional[np.random.Generator] = None) -> ServeResult:
        """Serve one request: ``serve_batch([x])[0]``."""
        return self.serve_batch([x], rng=rng)[0]

    def serve_batch(self, xs: Sequence[jnp.ndarray], *,
                    rng: Optional[np.random.Generator] = None
                    ) -> List[ServeResult]:
        """Serve R stacked requests. On the fused fast path this is ONE
        jitted dispatch (stacked portion forwards + device-side masking +
        quorum merge in a single compiled program); the legacy flag path
        issues one forward per arrived partition, one compiled
        mask-and-stack program and one quorum_aggregate launch.
        Failures are drawn per request (one vectorized sample for the whole
        batch), and results are returned WITHOUT waiting for the device —
        the logits sync is deferred to :class:`ServeResult` access.

        ``rng`` overrides the server's shared generator — the continuous
        -batching engine hands every micro-batch its own spawned stream, so
        failure draws are deterministic per batch id regardless of how chaos
        ticks and migrations interleave with dispatches.

        Re-entrant with :meth:`migrate`: all compiled state (portion
        forwards, stacked pytree, FC slices, plan arrays) is snapshotted
        before any compute, and migration installs fresh objects instead of
        mutating shared ones — an in-flight batch finishes on the plan it
        was dispatched under while queued requests pick up the migrated
        plan.

        Each host phase runs under a ``server.*`` profiler span
        (``jax.profiler.TraceAnnotation``; a no-op while no trace is being
        recorded): ``draw``, ``stack``, ``slot_forward`` (stat ``slot``),
        ``decode_ops``, ``merge`` (on the replicate loop with the stat
        ``masked_slots``) or ``fused_step``, and ``package``. None of them
        waits for the device."""
        R = len(xs)
        if R == 0:
            return []
        # -- migration handoff snapshot (one read of every mutable field) ----
        fastpath = self.fastpath_active
        rt = self._coded_runtime(self.ir)      # None for replicate-only plans
        rtc = self._compute_runtime(self.ir)   # None without compute coding
        step_coded = step_compute = None
        if fastpath:
            stacked, step = self._ensure_fused()
            if rt is not None:
                step_coded = self._coded_step()
            if rtc is not None:
                step_compute = self._compute_step()
            fc_q = self._fc_q
            jitted = None
        else:
            jitted = self.jitted_portions      # fully-compiled private list
            mask_stack = self._mask_stack_step()
            stacked = step = fc_q = None
        fc_weights, fc_bias = self.fc_weights, self.fc_bias
        arrays = self.arrays
        failure = self.failure
        knowledge_gap = bool(self.zeroed_slots)
        rng = self.rng if rng is None else rng
        # slot count from the SNAPSHOT (a re-read of portion_fns could see a
        # concurrent migration's new slot count against the old jitted list)
        Kp = len(jitted) if jitted is not None else len(fc_weights)

        with TraceAnnotation("server.stack"):
            sizes = [int(x.shape[0]) for x in xs]
            offs = np.concatenate([[0], np.cumsum(sizes)])
            # stack requests in numpy: an eager jnp.concatenate compiles one
            # XLA program per DISTINCT tuple of request shapes, which under
            # continuous batching (heterogeneous sizes) means a ~20ms
            # recompile on almost every micro-batch. On the fast path the
            # numpy stack crosses the jit boundary directly; the per-slot
            # loop puts it on the device once for its K calls
            x_all = xs[0] if R == 1 else np.concatenate(
                [np.asarray(x) for x in xs], axis=0)
            x_dev = None if fastpath else jnp.asarray(x_all)
        B = int(offs[-1])

        # a scenario deadline can only TIGHTEN the server's own SLO deadline
        # (taking the min) — it must never loosen it
        deadline = self.deadline
        scenario_deadline = getattr(failure, "deadline", None)
        if scenario_deadline is not None:
            deadline = min(deadline, scenario_deadline)
        share_arrived = share_t = None
        with TraceAnnotation("server.draw"):
            # a fully deterministic failure model (no forced set, no crash,
            # no outage channel) draws nothing and always yields the same
            # per-row outcome for a given (plan, deadline) — memoize it
            # instead of re-sampling and re-reducing per micro-batch (this
            # path is the failure-free hot loop; the generator is untouched
            # either way, so the cached rows are bit-identical to the
            # computed ones)
            if (type(failure) is FailureModel and not failure.forced_failures
                    and failure.crash_prob == 0 and not failure.outages):
                alive1, arrived1, lat1, share1, share_t1 = (
                    self._deterministic_outcome(arrays, deadline))
                alive = np.broadcast_to(alive1, (R, alive1.shape[0]))
                arrived = np.broadcast_to(arrived1, (R, arrived1.shape[0]))
                latency = np.broadcast_to(lat1, (R,))
                if share1 is not None:
                    share_arrived = np.broadcast_to(share1,
                                                    (R, share1.shape[0]))
                    share_t = np.broadcast_to(share_t1,
                                              (R, share_t1.shape[0]))
            else:
                alive, delay = failure.sample(rng, arrays, R)
                if rt is not None or rtc is not None:
                    _, arrived, latency, share_arrived, share_t = (
                        reduce_trials_coded(arrays, alive, delay, deadline,
                                            return_share_times=True))
                else:
                    _, arrived, latency = reduce_trials(arrays, alive, delay,
                                                        deadline)

            # per-sample row mask: request r's rows of portion k are zeroed
            # when k missed r's quorum (linear merge ⇒ exact per-request
            # masking). The clean (all-arrived) batch skips building it
            clean = bool(arrived.all())
            any_arrived = arrived.any(axis=0)               # (K,)
            # coded recovery engages only when a CODED slot's systematic
            # share is erased — while those all arrive the coded flow IS the
            # plain flow (identity decode), so it is skipped entirely:
            # failure-free coded serving — and any outage confined to
            # replicate slots or parity shares — is bit-identical to (and as
            # fast as) uncoded
            decode_needed = (rt is not None and share_arrived is not None
                             and not bool(
                                 share_arrived[:, rt.coded_slots].all()))
            # compute-coded slots decode from the k EARLIEST shard arrivals
            # (cancel-on-first-k). While those happen to be the systematic
            # shards — the all-alive steady state, by the planner's
            # placement — the decode is the identity and the plain path is
            # bit-exact, so it is skipped exactly like the output-coded fast
            # case above
            compute_decode = (rtc is not None and share_t is not None
                              and rtc.needs_decode(share_t))
        if fastpath:
            if fc_q is not None:
                fc_w, fc_scales = fc_q.q, fc_q.scale
            else:
                fc_w, fc_scales = fc_weights, None

        def forward(kslot):
            with TraceAnnotation("server.slot_forward", slot=kslot):
                return jitted[kslot](x_dev)   # padded to Dk inside the jit

        if decode_needed:
            # host-built per-request decode operators (memoized pinv per
            # arrival pattern), expanded to rows; everything else happens
            # inside the compiled program
            with TraceAnnotation("server.decode_ops"):
                dec_rows = np.repeat(
                    rt.decode_weights(share_arrived).transpose(1, 0, 2),
                    sizes, axis=1)                           # (K, B, R_sh)
                mask_rows = np.repeat(share_arrived, sizes, axis=0)
            if fastpath:
                with TraceAnnotation("server.fused_step"):
                    logits = step_coded(stacked, x_all, dec_rows, mask_rows,
                                        any_arrived, rt.enc_device, fc_w,
                                        fc_scales, fc_bias)
            else:
                # the oracle loop: every portion is computed (the parity
                # emulation combines them), then the SAME encode → decode →
                # merge math runs through the jitted ops wrappers
                portions = [forward(kslot) for kslot in range(Kp)]
                with TraceAnnotation("server.merge"):
                    stacked_p = jnp.stack(portions)          # (K, B, Dk)
                    parity = jnp.einsum("pk,kbf->pbf", rt.enc_device,
                                        stacked_p)
                    shares = jnp.concatenate([stacked_p, parity], axis=0)
                    decoded = K.coded_decode(shares, dec_rows, mask_rows)
                    logits = K.quorum_aggregate(
                        decoded, fc_weights, fc_bias,
                        jnp.asarray(any_arrived, jnp.int32))
        elif compute_decode:
            # host side: per-trial first-k decode operators (memoized pinv
            # per chosen-shard pattern) expanded to rows; the shard products
            # + parity emulation + decode + merge stay in ONE program
            with TraceAnnotation("server.decode_ops"):
                decs, masks = rtc.decode_weights(share_t)
                dec_rows = tuple(np.repeat(d.transpose(1, 0, 2), sizes,
                                           axis=1)
                                 for d in decs)             # (k, B, n) each
                mask_rows = tuple(np.repeat(m, sizes, axis=0) for m in masks)
                row_arr = np.repeat(arrived, sizes, axis=0)
            if fastpath:
                with TraceAnnotation("server.fused_step"):
                    logits = step_compute(stacked, x_all, dec_rows,
                                          mask_rows, row_arr, any_arrived,
                                          fc_w, fc_scales, fc_bias)
            else:
                # the oracle loop: full portion forwards, then the SAME
                # shard-split → parity → first-k decode math through the
                # jitted ops wrappers
                portions = [forward(kslot) for kslot in range(Kp)]
                with TraceAnnotation("server.merge"):
                    for e, dec, m in zip(rtc.entries, dec_rows, mask_rows):
                        portions[e.slot] = _compute_decode(
                            portions[e.slot],
                            jnp.asarray(e.G[e.k:], jnp.float32), e.k, dec, m,
                            K.coded_decode)
                    stacked_p = jnp.stack(portions)        # (K, B, Dk)
                    stacked_p = stacked_p * jnp.asarray(
                        row_arr.T[:, :, None], stacked_p.dtype)
                    logits = K.quorum_aggregate(
                        stacked_p, fc_weights, fc_bias,
                        jnp.asarray(any_arrived, jnp.int32))
        else:
            if fastpath:
                # numpy operands cross the jit boundary directly (fast-path
                # device_put) — no eager conversions before the single
                # dispatch
                with TraceAnnotation("server.fused_step"):
                    row_arrived = (None if clean
                                   else np.repeat(arrived, sizes, axis=0))
                    logits = step(stacked, x_all, row_arrived, any_arrived,
                                  fc_w, fc_scales, fc_bias,
                                  masked=not clean)
            else:
                # no eager op on this loop: a slot no row received is a
                # memoized zeros placeholder (its mask column is zero
                # anyway), and the masking and stacking run as ONE compiled
                # program with the numpy mask crossing the jit boundary
                Dk = int(fc_weights.shape[1])
                portions = tuple(
                    forward(kslot) if any_arrived[kslot]
                    else self._zero_portion(B, Dk)
                    for kslot in range(Kp))
                with TraceAnnotation("server.merge") as span:
                    # slots that some of the batch's requests received and
                    # others did not: where the program's masking engages
                    span.set_metadata(masked_slots=int(
                        (any_arrived & ~arrived.all(axis=0)).sum()))
                    stacked_p = mask_stack(
                        portions, np.repeat(arrived, sizes, axis=0))
                    logits = K.quorum_aggregate(
                        stacked_p, fc_weights, fc_bias,
                        any_arrived.astype(np.int32))
        with TraceAnnotation("server.package"):
            return self._package(xs, R, sizes, offs, logits, arrived,
                                 latency, alive, arrays,
                                 knowledge_gap=knowledge_gap,
                                 share_t=share_t)

    def _package(self, xs, R, sizes, offs, logits, arrived, latency, alive,
                 arrays, *, knowledge_gap: Optional[bool] = None,
                 share_t: Optional[np.ndarray] = None) -> List[ServeResult]:
        """One vectorized pass extracts every per-request scalar (the old
        per-request float()/all() calls were measurable at batch 32)."""
        if knowledge_gap is None:
            knowledge_gap = bool(self.zeroed_slots)
        lat_list = latency.tolist()
        complete = arrived.all(axis=1).tolist()
        offs_list = offs.tolist()
        return [ServeResult(
            latency=lat_list[r],
            arrived=arrived[r],
            # a migration-zeroed slot contributes nothing even when its
            # replicas arrive — that answer is degraded, not complete
            degraded=not complete[r] or knowledge_gap,
            _logits=logits,
            _span=(offs_list[r], offs_list[r + 1]),
            _alive=alive[r],
            _names=arrays.names,
            share_times=None if share_t is None else share_t[r],
        ) for r in range(R)]

    def _deterministic_outcome(self, arrays, deadline: float):
        """One cached (alive row, arrived row, latency, share-arrived row,
        share-time row) for the deterministic failure-free model. Keyed by
        the PlanArrays object — migrations install a fresh object, so stale
        plans can't hit. The share rows are None for replicate-only plans."""
        key = (id(arrays), deadline)
        hit = self._det_cache.get(key)
        if hit is None or hit[0] is not arrays:
            alive = np.ones((1, len(arrays.names)), bool)
            if arrays.layout is not None:
                _, arrived, latency, share, share_t = reduce_trials_coded(
                    arrays, alive, None, deadline, return_share_times=True)
                share_row, share_t_row = share[0], share_t[0]
            else:
                _, arrived, latency = reduce_trials(arrays, alive, None,
                                                    deadline)
                share_row = share_t_row = None
            hit = (arrays, alive[0], arrived[0], latency, share_row,
                   share_t_row)
            self._det_cache[key] = hit
        return hit[1], hit[2], hit[3], hit[4], hit[5]

    # -- elastic re-planning -------------------------------------------------

    def migrate(self, new_ir: PlanIR, mapping: Optional[Dict[int, int]] = None
                ) -> Dict:
        """Adopt a new plan without re-jitting untouched portion forwards.

        `mapping` maps NEW slot → OLD slot (e.g. from
        :func:`repro.runtime.failures.remap_students`); identity by default.
        A slot whose knowledge-partition mask is unchanged keeps its compiled
        portion forward and FC slice. A slot whose mask changed must NOT keep
        the mapped slot's FC slice — its portion features belong to the new
        partition, and multiplying them into the stale slot's FC columns
        produced wrong logits. Instead the slice is rebuilt from the
        content-addressed weight store (:attr:`redeploy_fn`, which also
        supplies the matching portion forward and — for fused servers — the
        slot's weight pytree); when no weights exist for the new partition
        the slice is zeroed — the slot contributes nothing until real
        weights arrive via :meth:`deploy_slot` — and the mapped slot's
        student stays deployed as the placement-only warm start.

        The fused fast path keeps its incremental-repair guarantee: only the
        touched rows of the stacked pytree are rebuilt (untouched rows are
        gathered in place), the compiled megastep survives whenever shapes
        are unchanged, and a store that cannot supply a refit slot's weight
        pytree drops the server back to the legacy loop instead of serving
        wrong fused weights.

        Out-of-range ``mapping`` sources raise ``ValueError`` (they used to
        be silently clamped to the last slot). Returns and stores migration
        stats: ``rejitted_slots`` (compiled forward invalidated — exactly
        the store-refit slots), ``reused_slots`` (mask unchanged, everything
        kept), ``refit_slots``, ``zeroed_slots`` (forward kept compiled,
        FC zeroed), ``fused_rows_rebuilt`` (stacked rows rewritten).

        Thread-safe against in-flight :meth:`serve_batch` calls: every field
        is replaced with a freshly-built object, never mutated in place."""
        old_ir = self.ir
        old_count = len(self.portion_fns)
        K_new = new_ir.K
        if mapping is None:
            mapping = {k: k for k in range(min(K_new, old_ir.K))}
        old_jit = self._jitted or [None] * old_count
        old_dims = list(self.part_dims) if self.part_dims is not None else \
            [int(self.fc_weights.shape[1])] * old_count
        C = int(self.fc_weights.shape[2])
        fused = self.fused
        fused_ok = fused is not None
        new_fns: List[Callable] = []
        new_jit: List[Optional[Callable]] = []
        slices: List[jnp.ndarray] = []
        dims: List[int] = []
        fused_params: List[Any] = []
        srcs: List[int] = []
        rejit, refit, zeroed = [], [], []
        for k in range(K_new):
            if k in mapping:
                src = int(mapping[k])
                if not 0 <= src < old_count:
                    raise ValueError(
                        f"migration mapping for slot {k} points at source "
                        f"slot {src}, but the server holds {old_count} "
                        f"portions")
            elif k < old_count:
                src = k
            else:
                src = -1        # grown slot: only the weight store can fill it
            same_mask = (0 <= src < old_ir.K
                         and new_ir.partition.shape[1] == old_ir.partition.shape[1]
                         and bool((new_ir.partition[k] == old_ir.partition[src]).all()))
            if same_mask:
                new_fns.append(self.portion_fns[src])
                new_jit.append(old_jit[src])
                slices.append(self.fc_weights[src])
                dims.append(old_dims[src])
                if fused_ok:
                    fused_params.append(fused.params[src])
                srcs.append(src)
                if src in self.zeroed_slots:
                    zeroed.append(k)   # carried slice is still all-zero:
                                       # the knowledge gap survives the move
                continue
            weights = (self.redeploy_fn(new_ir, k)
                       if self.redeploy_fn is not None else None)
            if weights is not None:
                fn, fc_slice = weights[0], weights[1]
                slot_params = weights[2] if len(weights) > 2 else None
                fc_slice = jnp.asarray(fc_slice, jnp.float32)
                new_fns.append(fn)
                new_jit.append(None)
                slices.append(fc_slice)
                dims.append(int(fc_slice.shape[0]))
                if fused_ok:
                    if slot_params is None:
                        # the store cannot feed the stacked pytree: fall
                        # back to the (always-correct) legacy loop
                        fused_ok = False
                    else:
                        fused_params.append(slot_params)
                srcs.append(-1)
                rejit.append(k)
                refit.append(k)
            elif src >= 0:
                # the src student stays deployed unchanged (only its FC
                # slice is zeroed), so its compiled wrapper is still valid
                # and the slot does NOT count as re-jitted
                new_fns.append(self.portion_fns[src])
                new_jit.append(old_jit[src])
                slices.append(jnp.zeros_like(self.fc_weights[src]))
                dims.append(old_dims[src])     # the deployed forward's width
                if fused_ok:
                    fused_params.append(fused.params[src])
                srcs.append(src)
                zeroed.append(k)
            else:
                raise ValueError(
                    f"slot {k} has no mapping source and the weight store "
                    f"holds nothing for its partition")
        Dk = max([int(s.shape[0]) for s in slices], default=1)
        Dk_old = int(self.fc_weights.shape[1])
        padded = [s if s.shape[0] == Dk
                  else jnp.pad(s, ((0, Dk - s.shape[0]), (0, 0))) for s in slices]
        if Dk != Dk_old:
            # carried legacy wrappers pad to the old uniform width
            new_jit = [None] * K_new
            if fused_ok and fused.pad is None:
                # a pad-less export (uniform-width ensembles) cannot follow
                # a width change — fall back to the legacy loop
                fused_ok = False
        new_fused = (FusedStudents(fused.apply, fused_params, fused.pad,
                                   fused.pre)
                     if fused_ok else None)
        new_stacked = (self._migrated_stacked(new_fused, srcs, refit, Dk,
                                              Dk_old, K_new, old_count)
                       if fused_ok else None)
        self.portion_fns = new_fns
        self._jitted = new_jit
        self.fc_weights = (jnp.stack(padded) if padded
                           else jnp.zeros((0, Dk, C), jnp.float32))
        self.part_dims = tuple(dims)
        self.zeroed_slots = frozenset(zeroed)
        self.plan = new_ir
        self._ir = new_ir
        self._arrays = None
        self._det_cache = {}       # keyed by the replaced PlanArrays object
        if new_fused is None and fused is not None and self.fastpath:
            # the export was dropped mid-migration (store without slot
            # params / width change on a pad-less export): un-pin the
            # explicit fastpath=True so serving falls back to the legacy
            # loop instead of raising at the next serve_batch
            self.fastpath = None
        self.fused = new_fused
        self._fused_stacked = new_stacked
        self._fc_q = None                       # re-quantized lazily
        if new_fused is None:
            self._fused_step = None
            self._fused_step_coded = None
            self._fused_step_compute = None
        self.last_migration = {"rejitted_slots": tuple(rejit),
                               "reused_slots": K_new - len(rejit) - len(zeroed),
                               "refit_slots": tuple(refit),
                               "zeroed_slots": tuple(zeroed),
                               "fused_rows_rebuilt":
                                   tuple(refit) if fused_ok else ()}
        if self.tracer is not None:
            self.tracer.instant(
                "migrate", f"{self.trace_name}server",
                rejitted=list(rejit), refit=list(refit),
                zeroed=list(zeroed),
                reused=K_new - len(rejit) - len(zeroed))
        return self.last_migration

    def _migrated_stacked(self, new_fused: FusedStudents, srcs: List[int],
                          refit: List[int], Dk: int, Dk_old: int,
                          K_new: int, old_count: int) -> Optional[Any]:
        """Rebuild ONLY the touched rows of the stacked pytree: carried rows
        are gathered from the old stack (no re-pad, no re-quantize), refit
        rows are padded/quantized fresh and written with ``.at[k].set``. A
        width or slot-count change forces a full restack (lazily, on the
        next serve)."""
        old = self._fused_stacked
        if old is None:
            return None                    # nothing built yet — stay lazy
        if Dk != Dk_old:
            return None                    # width changed: full restack
        refit_set = set(refit)
        # carried rows gather from their src; refit rows are overwritten
        # below, so any in-range placeholder works for them
        gather = np.asarray([s if s >= 0 else 0 for s in srcs], np.int64)
        int8 = self.quantize == "int8"

        def take(leaf):
            if _is_int8(leaf):
                return Int8Weights(leaf.q[gather], leaf.scale[gather])
            return leaf[gather]

        stacked = jax.tree.map(take, old, is_leaf=_is_int8)
        for k in refit_set:
            row = new_fused.padded(k, Dk)
            stacked = _set_stacked_row(
                stacked, k, quantize_tree(row) if int8 else row)
        return stacked

    def deploy_slot(self, k: int, fn: Callable, fc_slice: jnp.ndarray,
                    params: Optional[Any] = None) -> None:
        """Push (re-)distilled weights for slot ``k`` — the deployment
        layer's handshake for slots a migration left zeroed. Installs the
        portion forward (jit'd lazily), the FC slice, and — for fused
        servers — the slot's weight pytree (only that row of the stacked
        pytree is rewritten). Omitting ``params`` on a fused server drops
        it back to the legacy loop (the stacked export would be stale).
        Grows the uniform slice width when needed. Re-entrant with
        in-flight serves (fresh objects, no in-place mutation)."""
        if not 0 <= k < len(self.portion_fns):
            raise ValueError(f"slot {k} out of range "
                             f"(server holds {len(self.portion_fns)})")
        fc_slice = jnp.asarray(fc_slice, jnp.float32)
        d = int(fc_slice.shape[0])
        Dk = int(self.fc_weights.shape[1])
        weights = self.fc_weights
        grew = d > Dk
        if grew:
            weights = jnp.pad(weights, ((0, 0), (0, d - Dk), (0, 0)))
            Dk = d
        if d < Dk:
            fc_slice = jnp.pad(fc_slice, ((0, Dk - d), (0, 0)))
        self.fc_weights = weights.at[k].set(fc_slice)
        fns = list(self.portion_fns)
        fns[k] = fn
        self.portion_fns = fns
        jit = list(self._jitted or [None] * len(fns))
        jit[k] = None
        self._jitted = jit if not grew else [None] * len(fns)
        if self.part_dims is not None:
            dims = list(self.part_dims)
            dims[k] = d
            self.part_dims = tuple(dims)
        self.zeroed_slots = self.zeroed_slots - {k}
        if self.fused is not None:
            if params is None or (grew and self.fused.pad is None):
                # no slot pytree supplied, or the uniform width grew under a
                # pad-less export (its rows cannot be re-padded): the
                # stacked export would be stale — serve the legacy loop
                # (and un-pin an explicit fastpath=True so serving keeps
                # working instead of raising at the next batch)
                if self.fastpath:
                    self.fastpath = None
                self.fused = None
                self._invalidate_fused()
                return
            new_params = list(self.fused.params)
            new_params[k] = params
            self.fused = FusedStudents(self.fused.apply, new_params,
                                       self.fused.pad, self.fused.pre)
            if self._fused_stacked is not None and not grew:
                row = self.fused.padded(k, Dk)
                self._fused_stacked = _set_stacked_row(
                    self._fused_stacked, k,
                    quantize_tree(row) if self.quantize == "int8" else row)
            else:
                self._fused_stacked = None
        self._fc_q = None

    def remove_device(self, name: str, *, repair: bool = True):
        """Permanent loss. With ``repair=True`` (default) the loss routes
        through :class:`repro.runtime.controller.ClusterController`: groups
        that lost quorum are repaired incrementally (donor devices moved in,
        full Algorithm-1 replan as fallback) and this server migrates onto
        the repaired plan in place. Returns the controller's
        ``RepairOutcome`` — ``kind == "noop"`` when the loss broke no group
        (the server still adopts the shrunken plan).

        ``repair=False`` restores the legacy drop-only behaviour (returns
        ``None``) — the partition of an emptied group then permanently
        misses quorum."""
        if not repair:
            if isinstance(self.plan, PlanIR):
                self.plan = self.plan.drop_device(name)
                self._ir = self.plan
            else:
                for g in self.plan.groups:
                    g.devices = [d for d in g.devices if d.name != name]
                self._ir = None
            self._arrays = None
            self._det_cache = {}
            return None
        from repro.runtime.controller import ClusterController
        ctl = ClusterController(self.ir, server=self)
        return ctl.permanent_loss(name)

    def live_devices(self) -> List[Device]:
        """Devices with at least one placed share (systematic or parity)."""
        if isinstance(self.plan, PlanIR):
            devs = self.plan.devices()
            used = self.plan.member.any(0)
            cs = self.plan.coding
            if cs is not None and cs.P:
                used = used | cs.parity_member.any(0)
            return [devs[n] for n in np.flatnonzero(used)]
        return [d for g in self.plan.groups for d in g.devices]


def _compute_decode(y: jnp.ndarray, Gpar: jnp.ndarray, k: int,
                    dec, mask, decode: Callable) -> jnp.ndarray:
    """One compute-coded slot: split its (B, F) output column-wise into k
    data blocks, emulate the r pre-encoded parity shards (``Gpar`` (r, k)),
    and recover the k blocks with ``decode(shares, dec, mask)`` — a
    coded_decode entry point — from the slot's first-k decode weights
    ``dec`` (k, B, k + r) and arrival ``mask`` (B, k + r). Shares are laid
    out (k + r, B, w), the kernel's share-major layout."""
    F = y.shape[1]
    w = -(-F // k)
    blocks = jnp.pad(y, ((0, 0), (0, k * w - F))).reshape(-1, k, w)
    blocks = jnp.transpose(blocks, (1, 0, 2))                  # (k, B, w)
    par = jnp.einsum("rk,kbw->rbw", Gpar, blocks)
    decoded = decode(jnp.concatenate([blocks, par], axis=0), dec, mask)
    return jnp.transpose(decoded, (1, 0, 2)).reshape(-1, k * w)[:, :F]


def _padded_portion(fn: Callable, width: int, slot: int) -> Callable:
    """Slot ``slot``'s forward padded to ``width`` features. Its jitted
    program is named ``jit_padded_s<slot>``, so a device trace tells the
    slots apart (a slot a migration reuses keeps its old name)."""
    def padded(x):
        p = fn(x)
        if p.shape[-1] < width:
            p = jnp.pad(p, ((0, 0), (0, width - p.shape[-1])))
        return p
    padded.__name__ = padded.__qualname__ = f"padded_s{slot}"
    return padded


def server_from_ensemble(ens, deadline: float = float("inf"),
                         failure: Optional[FailureModel] = None,
                         seed: int = 0, fastpath: Optional[bool] = None,
                         quantize: str = "none") -> QuorumServer:
    """Build a QuorumServer from a core.pipeline.Ensemble.

    The server carries a content-addressed weight store over the ensemble's
    distilled students (keyed by partition filter set): a migration onto a
    plan whose partition matches one the ensemble was distilled for refits
    that slot's portion forward AND FC slice from the store instead of
    serving stale columns. When the ensemble's students are stackable (one
    arch family, see :meth:`repro.core.pipeline.Ensemble.fused_export`) the
    server also gets the fused fast path; ``quantize="int8"`` deploys the
    stacked students and FC slices weight-only quantized."""
    Dk = max(ens.part_dims)
    C = ens.fc["bias"].shape[0]
    Kp = len(ens.students)
    # split the FC kernel into per-partition slices, padded to uniform Dk
    weights = np.zeros((Kp, Dk, C), np.float32)
    off = 0
    for kslot, dim in enumerate(ens.part_dims):
        weights[kslot, :dim] = np.asarray(ens.fc["kernel"][off:off + dim])
        off += dim

    def make_fn(kslot):
        cfg, params, fwd = ens.students[kslot]
        def fn(x):
            _, feats, _ = fwd(params, cfg, x)
            return feats
        return fn

    portion_fns = [make_fn(i) for i in range(Kp)]
    fused = ens.fused_export() if hasattr(ens, "fused_export") else None
    ir = getattr(ens, "ir", None)
    groups = sorted(ens.plan.groups, key=lambda g: g.partition_idx)
    store: Dict[frozenset, Tuple] = {}
    for kslot in range(Kp):
        if ir is not None and kslot < ir.K:
            filters = np.flatnonzero(ir.partition[kslot])
        else:
            filters = np.asarray(groups[kslot].filters, np.int64)
        store[frozenset(filters.tolist())] = (
            portion_fns[kslot],
            jnp.asarray(weights[kslot, :ens.part_dims[kslot]]),
            fused.params[kslot] if fused is not None else None)

    def redeploy(new_ir: PlanIR, slot: int):
        key = frozenset(np.flatnonzero(new_ir.partition[slot]).tolist())
        return store.get(key)

    return QuorumServer(
        plan=ir or ens.plan,
        portion_fns=portion_fns,
        fc_weights=jnp.asarray(weights),
        fc_bias=jnp.asarray(ens.fc["bias"]),
        deadline=deadline,
        failure=failure or FailureModel(),
        rng=np.random.default_rng(seed),
        part_dims=tuple(int(d) for d in ens.part_dims),
        redeploy_fn=redeploy,
        fused=fused,
        fastpath=fastpath,
        quantize=quantize,
    )
