"""Kernel efficiency vs the measured roofline, before/after block tuning.

For each serving kernel (`quorum_aggregate`, `coded_decode`,
`dequant_matmul`) over a small shape sweep:

1. time the kernel at today's default block sizes,
2. run the block-size autotuner (:mod:`repro.kernels.autotune`) for the
   shape and time the kernel again with the tuned table installed,
3. compare the tuned time against the *measured* roofline bound — the
   host :class:`~repro.core.hwspec.DeviceSpec` fitted by the microbench
   harness predicts ``floor + flops/peak_flops + 8·bytes/peak_bw`` for the
   kernel's analytic FLOP/byte footprint; ``efficiency = bound / measured``
   is the achieved fraction of that bound.

Emits one CSV row per (kernel, shape) plus the acceptance gates:

- ``bench_roofline/gate_no_regression`` — the tuned configuration is no
  slower than the default on EVERY benchmarked shape (1.15× timing
  tolerance; the tuner's hysteresis keeps the default unless a challenger
  wins by >5%, so a regression here means the table is hurting).
- ``bench_roofline/gate_speedup`` — tuning is measurably faster on at
  least one shape (>5%).

Gate violations raise, so ``benchmarks/run.py`` and the CI smoke job fail
loudly instead of shipping a table that regresses the serving path.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import BUDGET, emit

REPEATS = {"cpu": 5, "full": 20}[BUDGET]


def _shapes():
    """(kernel, tag, builder) cells. Large batches are where the block
    choice moves the needle (fewer grid steps); a small batch per kernel
    checks the tuner leaves the short path alone."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)

    def qa(B):
        K_, Dk, C = 4, 16, 10
        portions = jnp.asarray(rng.standard_normal((K_, B, Dk)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((K_, Dk, C)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal(C), jnp.float32)
        mask = np.ones(K_, np.int32)
        flops = 2.0 * K_ * B * Dk * C
        nbytes = 4.0 * (K_ * B * Dk + K_ * Dk * C + C + B * C)
        return ("quorum_aggregate", (portions, w, bias, mask), flops, nbytes)

    def cd(B):
        R, K_, F = 6, 4, 16
        shares = jnp.asarray(rng.standard_normal((R, B, F)), jnp.float32)
        dec = jnp.asarray(rng.standard_normal((K_, B, R)), jnp.float32)
        mask = jnp.ones((B, R), jnp.float32)
        flops = 2.0 * B * K_ * R * F
        nbytes = 4.0 * (B * R * F + B * K_ * R + B * R + B * K_ * F)
        return ("coded_decode", (shares, dec, mask), flops, nbytes)

    def dq(B, N):
        D = 64
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
        q = jnp.asarray(rng.integers(-127, 128, (D, N)), jnp.int8)
        sc = jnp.asarray(rng.uniform(0.01, 0.1, (N,)), jnp.float32)
        flops = 2.0 * B * D * N
        nbytes = 4.0 * B * D + 1.0 * D * N + 4.0 * N + 4.0 * B * N
        return ("dequant_matmul", (x, q, sc), flops, nbytes)

    return [
        (*qa(1024), "B1024"),
        (*qa(64), "B64"),
        (*cd(1024), "B1024"),
        (*cd(64), "B64"),
        (*dq(1024, 256), "B1024xN256"),
        (*dq(64, 512), "B64xN512"),
    ]


def main() -> None:
    from repro.kernels import autotune as AT
    from repro.kernels import ops as K
    from repro.launch.microbench import (fit_host_spec,
                                         portion_forward_samples,
                                         time_callable)

    # the measured host spec anchoring the roofline bound
    spec = fit_host_spec(portion_forward_samples(repeats=3))
    emit("bench_roofline/host_spec", spec.latency_floor * 1e6,
         f"peak_flops={spec.peak_flops:.3e};peak_bw={spec.peak_bw:.3e}")

    tuners = {"quorum_aggregate": AT.tune_quorum_aggregate,
              "coded_decode": AT.tune_coded_decode,
              "dequant_matmul": AT.tune_dequant_matmul}
    keyers = {"quorum_aggregate": lambda a: AT.key_quorum_aggregate(a[0], a[1]),
              "coded_decode": lambda a: AT.key_coded_decode(a[0], a[1]),
              "dequant_matmul": lambda a: AT.key_dequant_matmul(a[0], a[1])}

    table = AT.TuningTable()
    saved = AT.active_table()
    AT.set_table(table)
    rows = []
    try:
        for kernel, args, flops, nbytes, tag in _shapes():
            fn = getattr(K, kernel)
            defaults = AT.DEFAULTS[kernel]
            t_default = time_callable(lambda: fn(*args, **defaults),
                                      repeats=REPEATS)
            tuners[kernel](table, *args, repeats=REPEATS)
            shape, dtype = keyers[kernel](args)
            blocks = table.get(kernel, shape, dtype)
            if blocks == defaults:
                # the tuner kept the default (hysteresis): the resolved call
                # is the identical code path, so re-timing it would only
                # compare two noise draws of the same kernel
                t_tuned = t_default
            else:
                # block sizes now resolve through the freshly-tuned table
                t_tuned = time_callable(lambda: fn(*args), repeats=REPEATS)
            bound = float(spec.latency(flops, nbytes))
            eff = bound / t_tuned if t_tuned > 0 else 0.0
            rows.append((kernel, tag, t_default, t_tuned))
            emit(f"bench_roofline/{kernel}_{tag}", t_tuned * 1e6,
                 f"default_us={t_default*1e6:.1f};"
                 f"speedup={t_default/max(t_tuned,1e-12):.3f};"
                 f"bound_us={bound*1e6:.1f};efficiency={eff:.4f};"
                 f"blocks={'/'.join(f'{k}={v}' for k, v in sorted(blocks.items()))}")
    finally:
        AT.set_table(saved)

    # acceptance gates
    regressions = [(k, tag) for k, tag, td, tt in rows if tt > td * 1.15]
    best = max((td / max(tt, 1e-12) for _, _, td, tt in rows), default=0.0)
    emit("bench_roofline/gate_no_regression", 0.0,
         "ok" if not regressions else f"FAILED:{regressions}")
    emit("bench_roofline/gate_speedup", 0.0,
         f"best_speedup={best:.3f};{'ok' if best > 1.05 else 'FAILED'}")
    if regressions:
        raise RuntimeError(
            f"tuned blocks slower than defaults on {regressions}")
    if best <= 1.05:
        raise RuntimeError("tuning produced no measurable speedup anywhere")


if __name__ == "__main__":
    main()
