"""Compile-only checks of the serving kernels and megasteps for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described and not attached. These tests compile, with ``interpret=False``,
the three serving kernels and the fused and output-coded megasteps at the
chip smoke run's widths (K=8 slots, Dk=32-64, C=10, B=128), and check
that every compiled program carries a Mosaic kernel (``tpu_custom_call``).
Nothing runs: a pass says the chip's compiler accepts the program, not
that it is fast or right. The topology is described inside a fixture, so
only the worker that runs this file loads the TPU library, and the tests
skip where it cannot be described.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.coding.planner import select_redundancy
from repro.core.assignment import StudentArch
from repro.core.grouping import Device
from repro.core.plan_ir import (PlanIR, device_matrix, eq1a_latency,
                                student_matrix)
from repro.kernels import coded_decode as cd
from repro.kernels import dequant_matmul as dq
from repro.kernels import quorum_aggregate as qa
from repro.runtime.engine import build_demo_server

K, B, DK, C, M = 8, 128, 32, 10, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args, **kw) -> str:
    return jax.jit(fn, static_argnames=tuple(kw)).lower(
        *args, **kw).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_quorum_aggregate_compiles(spec, dtype):
    args = [spec((K, B, DK)), spec((K, DK, C), dtype), spec((C,)),
            spec((K,), jnp.int32), spec((K,))]
    text = _compiled_text(
        lambda p, w, b, m, s: qa.quorum_aggregate(p, w, b, m, s,
                                                  interpret=False), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_coded_decode_compiles(spec, dtype):
    R = K + 2
    args = [spec((R, B, DK), dtype), spec((K, B, R)), spec((B, R), jnp.int32),
            spec((R,))]
    text = _compiled_text(
        lambda s, d, m, sc: cd.coded_decode(s, d, m, sc, interpret=False),
        *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("scale_shape", [(), (512,)],
                         ids=["per_tensor", "per_channel"])
def test_dequant_matmul_compiles(spec, scale_shape):
    args = [spec((B, M)), spec((M, 512), jnp.int8), spec(scale_shape)]
    text = _compiled_text(
        lambda x, q, s: dq.dequant_matmul(x, q, s, interpret=False), *args)
    assert "tpu_custom_call" in text


def _singleton_ir(slots: int = K, spares: int = 4) -> PlanIR:
    """``slots`` single-device groups over M filters plus unassigned spares:
    the shape the planner gives the paper's 8-device fleet."""
    n = slots + spares
    devs = [Device(f"d{i}", (1 + i % 3) * 1e7, 2e6, 500, 0.2)
            for i in range(n)]
    names, dcaps = device_matrix(devs)
    snames, scaps = student_matrix(
        [StudentArch("s", 5e6, 0.6e6, 64, 0.15e6)])
    member = np.zeros((slots, n), bool)
    part = np.zeros((slots, M), bool)
    for k in range(slots):
        member[k, k] = True
        part[k, (M // slots) * k:(M // slots) * (k + 1)] = True
    return PlanIR(names, dcaps, snames, scaps, member, part,
                  np.zeros(slots, np.int64), np.arange(slots, dtype=np.int64),
                  eq1a_latency(scaps, dcaps), np.zeros((M, M)), 1.0, 0.25)


def _abstract(spec, tree):
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)


def _build_for_tpu(monkeypatch, build):
    """Build a server's step as it is built on a TPU host: the builders
    pick interpret mode from the default backend."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return build()


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_fused_megastep_compiles(spec, monkeypatch, quantize):
    srv = build_demo_server(_singleton_ir(), feat=M, hidden=M, n_classes=C,
                            quantize=quantize)
    stacked, step = _build_for_tpu(monkeypatch, srv._ensure_fused)
    if srv._fc_q is not None:
        fc_w, fc_s = spec(srv._fc_q.q.shape, jnp.int8), spec((K,))
    else:
        fc_w, fc_s = spec(srv.fc_weights.shape), None
    text = step.lower(_abstract(spec, stacked), spec((B, M)),
                      spec((B, K), bool), spec((K,), bool), fc_w, fc_s,
                      spec((C,)), masked=True).compile().as_text()
    assert "tpu_custom_call" in text


def test_output_coded_megastep_compiles(spec, monkeypatch):
    ir = select_redundancy(_singleton_ir(), code_k=4, parity=1)
    assert ir.coding is not None and ir.coding.n_groups == 2
    srv = build_demo_server(ir, feat=M, hidden=M, n_classes=C)
    stacked, _ = _build_for_tpu(monkeypatch, srv._ensure_fused)
    step = _build_for_tpu(monkeypatch, srv._coded_step)
    rt = srv._coded_runtime(ir)
    R = rt.n_shares
    text = step.lower(_abstract(spec, stacked), spec((B, M)),
                      spec((K, B, R)), spec((B, R), bool), spec((K,), bool),
                      spec(rt.enc_device.shape), spec(srv.fc_weights.shape),
                      None, spec((C,))).compile().as_text()
    assert "tpu_custom_call" in text
