"""Compile-only rehearsal of the TPU path at MobileNetV2 alpha1.0/224 widths.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, value slices Mosaic cannot
lay out, operand types the MXU does not take, fast memory a kernel
overruns. These tests compile the three CU kernels with `interpret=False`,
one stage program of each CU role, the whole-network program the engine
serves, and the Tail stage sharded over the 4-replica 'data' mesh, for a
described (not attached) v5e. Nothing runs;
a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so the test worker that is
given this file is the only one that loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.depthwise_conv import depthwise_conv_q
from repro.kernels.fused_irb import fused_irb_q
from repro.kernels.pointwise_conv import pointwise_conv_q

BATCH = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _vecs(n, sharding, corr=jnp.float32):
    """(mult, zero-point correction, bias) epilogue vectors: the correction
    is f32 (dw) or the matmul kernels' integer z_x * wsum."""
    return (jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((n,), corr, sharding=sharding),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("hw,k,n", [
    (56, 24, 144),    # irb expand at 56^2
    (56, 144, 24),    # irb project at 56^2
    (7, 576, 160),    # irb13 project
    (7, 960, 320),    # irb16 project
    (28, 128, 256),   # 128-aligned blocks
])
def test_pointwise_compiles(one_chip, hw, k, n):
    x = jax.ShapeDtypeStruct((BATCH, hw, hw, k), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    _compile(lambda *a: pointwise_conv_q(*a, qmax=15, interpret=False),
             x, w, *_vecs(n, one_chip, jnp.int32))


@pytest.mark.parametrize("hw,c,stride", [
    (112, 32, 1),     # irb0 dw
    (112, 96, 2),     # irb1 dw
    (56, 144, 1),     # irb2 dw
    (56, 144, 2),     # irb3 dw
    (14, 384, 1),     # irb7 dw
    (14, 576, 2),     # irb13 dw
    (7, 960, 1),      # irb14 dw
])
def test_depthwise_compiles(one_chip, hw, c, stride):
    x = jax.ShapeDtypeStruct((BATCH, hw, hw, c), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, c), jnp.int32, sharding=one_chip)
    _compile(lambda *a: depthwise_conv_q(*a, kernel=3, stride=stride,
                                         interpret=False),
             x, w, *_vecs(c, one_chip))


@pytest.mark.parametrize("hw,c,e,co,stride,residual", [
    (112, 16, 96, 24, 2, False),   # irb1: the 112^2 stride-2 block
    (56, 24, 144, 24, 1, True),    # irb2
    (14, 64, 384, 64, 1, True),    # irb7
    (7, 160, 960, 160, 1, True),   # irb14
])
def test_fused_irb_compiles(one_chip, hw, c, e, co, stride, residual):
    s = one_chip
    x = jax.ShapeDtypeStruct((BATCH, hw, hw, c), jnp.int32, sharding=s)
    w1 = jax.ShapeDtypeStruct((c, e), jnp.int32, sharding=s)
    w2 = jax.ShapeDtypeStruct((3, 3, e), jnp.int32, sharding=s)
    w3 = jax.ShapeDtypeStruct((e, co), jnp.int32, sharding=s)
    rc = (1.0, 0.5, -2.0, 0.75, 3) if residual else None
    _compile(lambda *a: fused_irb_q(*a, stride=stride, residual=residual,
                                    res_consts=rc, interpret=False),
             x, w1, *_vecs(e, s, jnp.int32), w2, *_vecs(e, s), w3,
             *_vecs(co, s, jnp.int32))


@pytest.fixture(scope="module")
def mnv2_qnet():
    from repro.configs import mobilenet_v2
    from repro.models import layers

    return layers.make_calibrated_qnet(mobilenet_v2.get_config(alpha=1.0))


def _pallas_stages(qnet, mesh=None):
    """Stage executors forced onto the Pallas routes (this process's
    backend is the CPU, so "auto" would pick XLA). Host QNet constants
    (prepare=False) lower as HLO constants, which a described device can
    take; device arrays could not be placed there."""
    from repro.serve.vision.stages import compile_stages

    return compile_stages(
        qnet, body_fast_path="on", op_kernels="on", prepare=False,
        donate="off", interpret=False, mesh=mesh)


@pytest.fixture(scope="module")
def mnv2_stages(mnv2_qnet):
    return mnv2_qnet, _pallas_stages(mnv2_qnet)


@pytest.mark.parametrize("role", ["head", "body", "tail", "classifier"])
def test_stage_program_compiles(one_chip, mnv2_stages, role):
    from repro.core import compiler as CC

    qnet, stages = mnv2_stages
    cu_name = {"head": CC.HEAD, "body": CC.BODY, "tail": CC.TAIL,
               "classifier": CC.CLASSIFIER}[role]
    i = next(i for i, st in enumerate(stages) if st.spec.cu == cu_name)
    shape = jax.eval_shape(
        lambda x: _chain(stages[:i], x),
        jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32))
    x = jax.ShapeDtypeStruct(shape.shape, shape.dtype, sharding=one_chip)
    compiled = jax.jit(stages[i]._trace).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_net_program_compiles(one_chip, mnv2_stages):
    """The served program: the four stages chained in one `stage_net`.
    Each CU keeps its kernels, under its own scope in the op metadata:
    the stem's irb0 (depthwise + pointwise), 16 fused IRBs, one
    pointwise Tail and one Classifier matmul."""
    from repro.serve.vision.stages import CompiledNet

    _, stages = mnv2_stages
    x = jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    text = CompiledNet(stages)._fn.lower(x).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert {st.spec.cu: sum(f"/{st.spec.cu}/" in ln for ln in calls)
            for st in stages} == {"head": 2, "body": 16, "tail": 1,
                                  "classifier": 1}
    assert len(calls) == 20


def _chain(stages, x):
    for st in stages:
        x = st._trace(x)
    return x


def test_data_mesh_stage_compiles(topo, mnv2_qnet):
    """The --replicas 4 path: a stage sharded over a 4-chip 'data' mesh.
    The compiler cannot partition a Pallas kernel, so each replica must
    run it on its own rows (shard_map) — and no collective is needed."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.dist.sharding import batch_sharding

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    stages = _pallas_stages(mnv2_qnet, mesh)
    tail = len(stages) - 2
    shape = jax.eval_shape(
        lambda x: _chain(stages[:tail], x),
        jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32))
    x = jax.ShapeDtypeStruct(shape.shape, shape.dtype,
                             sharding=batch_sharding(mesh))
    text = stages[tail]._fn.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


def test_data_mesh_net_compiles(topo, mnv2_qnet):
    """The --replicas 4 path of the served program: one `shard_map`
    around the whole chain, each replica on its own rows, no collective."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.dist.sharding import batch_sharding
    from repro.serve.vision.stages import CompiledNet

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    net = CompiledNet(_pallas_stages(mnv2_qnet, mesh))
    x = jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32,
                             sharding=batch_sharding(mesh))
    text = net._fn.lower(x).compile().as_text()
    assert text.count("tpu_custom_call") > 0
    assert "all-gather" not in text and "all-reduce" not in text
