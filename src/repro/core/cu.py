"""Heterogeneous Compute Unit runners — integer QNet execution (Sec. 4).

The FPGA executes each CU as a fused pipeline: operators stream intermediate
feature maps through FIFOs; only CU inputs/outputs touch shared DDR. The TPU
analogue: each CU is ONE jitted function (one XLA program == one 'CU
invocation'), so all intra-CU intermediates stay on-chip; for the Body CU the
`kernels/fused_irb` Pallas kernel additionally pins the expanded intermediate
into VMEM explicitly.

All arithmetic inside a CU is integer: int MACs -> int32 accum -> requantize
-> clip (the Approximator & Clip unit == fused ReLU6), following
`core/integer_ops`. Zero floating point remains in the datapath except the
requant multiplier (which also has a faithful fixed-point mode; in that mode
the residual skip-add is integer too, via `int_residual_add`).

Two execution tiers share this module:

  * `QNet` (host numpy metadata) — the semantic reference. Every invocation
    re-uploads weights/requant constants, exactly what a cold host would do.
  * `PreparedQNet` (`prepare_qnet`) — the serving artifact: every constant a
    CU invocation needs is converted to a device-resident jnp array ONCE at
    plan-build time, and the operator bodies switch to the compiled integer
    fast-path formulations of `core/integer_ops` (shifted-slice depthwise,
    exactness-gated f32 matmul/conv). The accumulators are bit-identical to
    the reference, so `run_qnet(prepare_qnet(q), x) == run_qnet(q, x)`
    element-for-element — verified by tests/test_prepared_fastpath.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as G
from repro.core.integer_ops import (
    f32_accum_exact,
    int_conv1d,
    int_conv1d_f32,
    int_conv2d,
    int_conv2d_f32,
    int_depthwise1d_shifts,
    int_depthwise_shifts,
    int_pointwise,
    int_pointwise_f32,
    int_residual_add,
    quantized_op_epilogue,
    residual_fixed_consts,
)
from repro.core.qnet import QNet, QOp


def _rounded(*products):
    """Materialize f32 products before they are added. Eagerly every op
    rounds on its own; under jit a backend may contract `x * y + z` into
    one fused multiply-add (XLA:CPU does), which rounds once and can flip
    a later round() — the jitted stages would drift off the eager
    reference by 1 LSB. The barrier keeps jit's rounding the eager one."""
    out = jax.lax.optimization_barrier(products)
    return out[0] if len(out) == 1 else out


def quantize_input(x: jnp.ndarray, scale: float, zp: float, bits: int = 8):
    q = jnp.round(x / scale - zp)
    return jnp.clip(q, 0, 2**bits - 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# PreparedQNet: device-resident constants + compiled fast-path dispatch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PreparedQOp:
    """One QOp with every kernel/epilogue constant already on device.

    Field names mirror `QOp` so the two are interchangeable wherever the
    runners only read metadata; the arrays are jnp (committed to the default
    device), so jitted stage traces close over device constants instead of
    re-uploading host numpy each invocation.
    """

    spec: G.OpSpec
    w_q: jnp.ndarray  # int32, original weight layout (conv HWIO / dw HW1C)
    w_kern: jnp.ndarray  # kernel layout: dw [K,K,C]; pw/dense [Cin,Cout]
    w_scale: jnp.ndarray  # [M] f32
    wsum: jnp.ndarray  # [M] i32
    bias_q: jnp.ndarray  # [M] i32
    mult: jnp.ndarray  # [M] f32
    zcorr: jnp.ndarray  # [M] f32 — in_zp * mult * wsum (float epilogue form)
    zpc: jnp.ndarray  # [M] i32 — int32(in_zp) * wsum (integer epilogue form)
    z_x: jnp.ndarray  # scalar i32 — int32(in_zp)
    mantissa: jnp.ndarray  # [M] i64/i32 fixed-point mantissa
    shift: jnp.ndarray  # [M] i32
    in_scale: float
    in_zp: float
    out_scale: float
    out_zp: float
    clip: bool
    in_qmax: int  # upper bound of the incoming activation tensor
    f32_exact: bool  # f32 accumulation provably bit-exact for this op

    @property
    def qmax(self) -> int:
        return 2**self.spec.act_bits - 1


@dataclasses.dataclass(frozen=True)
class PreparedQNet:
    """A QNet lowered for serving: per-op `PreparedQOp`s + per-residual
    integer skip-add constants. Drop-in for `QNet` in every runner here and
    in `kernels/ops.py` / `serve/vision/stages.py`.

    `routes` (op name -> (route, params)) carries a measured route
    selection resolved from a `repro.tune.TunedPlan` at prepare time: the
    runners execute a routed op through that route instead of the default
    formulation. Ops absent from the map fall back to the defaults, so a
    partial (or empty) map is always safe."""

    qnet: QNet
    ops: Dict[str, PreparedQOp]
    res_q: Dict[str, Tuple[float, float]]
    res_fixed: Dict[str, Tuple[int, int, int, int, int]]
    routes: Dict[str, Tuple[str, Dict[str, int]]] = dataclasses.field(
        default_factory=dict)

    @property
    def spec(self) -> G.NetSpec:
        return self.qnet.spec


def _prepare_qop(qop: QOp, in_qmax: int, put=jnp.asarray) -> PreparedQOp:
    w_np = np.asarray(qop.w_q)
    if qop.spec.kind == G.DW:
        w_kern = w_np.reshape(w_np.shape[0], w_np.shape[1], w_np.shape[-1])
    elif qop.spec.kind == G.DW1D:
        w_kern = w_np.reshape(w_np.shape[0], w_np.shape[-1])  # [K, C]
    elif qop.spec.kind in (G.PW, G.DENSE):
        w_kern = w_np[0, 0] if w_np.ndim == 4 else w_np
    else:
        w_kern = w_np
    zpc = np.int32(qop.in_zp) * np.asarray(qop.wsum, np.int32)
    return PreparedQOp(
        spec=qop.spec,
        w_q=put(jnp.asarray(w_np, jnp.int32)),
        w_kern=put(jnp.asarray(w_kern, jnp.int32)),
        w_scale=put(jnp.asarray(qop.w_scale, jnp.float32)),
        wsum=put(jnp.asarray(qop.wsum, jnp.int32)),
        bias_q=put(jnp.asarray(qop.bias_q, jnp.int32)),
        mult=put(jnp.asarray(qop.mult, jnp.float32)),
        zcorr=put(jnp.asarray(qop.in_zp * qop.mult * qop.wsum, jnp.float32)),
        zpc=put(jnp.asarray(zpc, jnp.int32)),
        z_x=put(jnp.asarray(qop.in_zp, jnp.int32)),
        mantissa=put(jnp.asarray(qop.mantissa)),
        shift=put(jnp.asarray(qop.shift, jnp.int32)),
        in_scale=qop.in_scale,
        in_zp=qop.in_zp,
        out_scale=qop.out_scale,
        out_zp=qop.out_zp,
        clip=qop.clip,
        in_qmax=in_qmax,
        f32_exact=f32_accum_exact(w_np, in_qmax),
    )


def _constant_put(mesh):
    """Constant placement for `prepare_qnet`: default device when mesh is
    None, else replicated across every replica of the 'data' mesh (so jitted
    sharded stage traces close over replica-local constants — the
    multi-replica analogue of DeepDive's per-CU weight buffers)."""
    if mesh is None:
        return lambda a: a
    from repro.dist.sharding import replicate
    return partial(replicate, mesh=mesh)


def replicate_prepared(pq: "PreparedQNet", mesh) -> "PreparedQNet":
    """Re-place an already-prepared net's constants replicated on `mesh`."""
    put = _constant_put(mesh)
    ops = {
        name: dataclasses.replace(
            pop, **{f: put(getattr(pop, f)) for f in (
                "w_q", "w_kern", "w_scale", "wsum", "bias_q", "mult",
                "zcorr", "zpc", "z_x", "mantissa", "shift")})
        for name, pop in pq.ops.items()
    }
    return dataclasses.replace(pq, ops=ops)


def _validate_routes(op_routes, ops: Dict[str, PreparedQOp]) -> Dict:
    """Attach-time validation of resolved routes against the *actual*
    prepared constants: an `int_f32` route whose op fails the 2^24
    exactness bound here (different weights than the tuned net) is
    dropped rather than run inexactly; unknown op names are ignored."""
    routes: Dict[str, Tuple[str, Dict[str, int]]] = {}
    for name, (route, params) in op_routes.items():
        pop = ops.get(name)
        if pop is None:
            continue
        if route == "int_f32" and not pop.f32_exact:
            continue
        routes[name] = (route, dict(params))
    return routes


def _resolve_tuned_routes(tuned, qnet,
                          ops: Dict[str, PreparedQOp]) -> Dict:
    """Project a `TunedPlan` onto prepared ops (op name -> (route, params))."""
    op_routes, _ = tuned.resolve(qnet)
    return _validate_routes(op_routes, ops)


def prepare_qnet(qnet: QNet, input_bits: int = 8, mesh=None,
                 tuned=None, routes=None) -> PreparedQNet:
    """Lower a QNet to its device-resident serving form (one-time cost).

    Walks the graph to bound each op's input activations (needed for the
    f32-exactness gate) and uploads every constant once. Idempotent on an
    already-prepared net (unless `mesh` is given, which re-places the
    constants replicated across the mesh's replicas).

    `tuned` (a `repro.tune.TunedPlan`) resolves the measured per-op route
    selection onto the prepared net: the runners then execute each routed
    op through its tuned route (see `PreparedQNet.routes`). Callers that
    already resolved a plan (the stage compiler) pass the op-name-keyed
    `routes` dict directly instead; both paths re-validate eligibility
    against the prepared constants.
    """
    if isinstance(qnet, PreparedQNet):
        pq = qnet if mesh is None else replicate_prepared(qnet, mesh)
        if routes is not None:
            pq = dataclasses.replace(
                pq, routes=_validate_routes(routes, pq.ops))
        elif tuned is not None:
            pq = dataclasses.replace(pq, routes=_resolve_tuned_routes(
                tuned, pq.qnet, pq.ops))
        return pq
    put = _constant_put(mesh)
    ops: Dict[str, PreparedQOp] = {}
    res_fixed: Dict[str, Tuple[int, int, int, int, int]] = {}
    cur_bits = input_bits
    for block in qnet.spec.blocks:
        for op in block.ops:
            qop = qnet.ops[op.name]
            ops[op.name] = _prepare_qop(qop, 2**cur_bits - 1, put)
            cur_bits = op.act_bits
            if block.se is not None and block.se_after == op.name:
                sq, ex = block.se.squeeze, block.se.excite
                # squeeze reads the (pooled) dw output; excite reads squeeze
                ops[sq.name] = _prepare_qop(
                    qnet.ops[sq.name], 2**cur_bits - 1, put)
                ops[ex.name] = _prepare_qop(
                    qnet.ops[ex.name], 2**sq.act_bits - 1, put)
        if block.residual:
            last = qnet.ops[block.ops[-1].name]
            first = qnet.ops[block.ops[0].name]
            y_s, y_z = qnet.res_q[block.name]
            res_fixed[block.name] = residual_fixed_consts(
                first.in_scale, first.in_zp,
                last.out_scale, last.out_zp, y_s, y_z)
    if routes is not None:
        attached = _validate_routes(routes, ops)
    elif tuned is not None:
        attached = _resolve_tuned_routes(tuned, qnet, ops)
    else:
        attached = {}
    return PreparedQNet(qnet=qnet, ops=ops, res_q=dict(qnet.res_q),
                        res_fixed=res_fixed, routes=attached)


def _accumulate(x_q: jnp.ndarray, qop, route: Optional[str] = None
                ) -> jnp.ndarray:
    """Int32 accumulator for one op.

    `QOp` (host metadata) takes the reference XLA integer ops; `PreparedQOp`
    takes the compiled fast-path formulations — shifted-slice depthwise and,
    when the per-op exactness bound holds, f32-unit matmul/conv — which
    produce the *same* int32 accumulator (see core/integer_ops docstrings).

    `route` (PreparedQOp only) forces one of the named tuned-cache
    accumulator routes instead of the heuristic default — every route is an
    alternate formulation of the identical accumulator, so the choice can
    never move a bit, only the wall clock.
    """
    op = qop.spec
    if route is not None:
        assert isinstance(qop, PreparedQOp), "routes bind to prepared ops"
        if route == "int_ref":
            if op.kind == G.CONV:
                return int_conv2d(x_q, qop.w_q, stride=op.stride)
            if op.kind == G.DW:
                return int_conv2d(x_q, qop.w_q, stride=op.stride,
                                  groups=op.in_ch)
            if op.kind == G.CONV1D:
                return int_conv1d(x_q, qop.w_q, stride=op.stride)
            if op.kind == G.DW1D:
                return int_conv1d(x_q, qop.w_q, stride=op.stride,
                                  groups=op.in_ch)
            return int_pointwise(x_q, qop.w_kern)
        if route == "dw_shifts":
            if op.kind == G.DW1D:
                return int_depthwise1d_shifts(x_q, qop.w_kern,
                                              stride=op.stride)
            return int_depthwise_shifts(x_q, qop.w_kern, stride=op.stride)
        if route == "int_f32":
            if op.kind == G.CONV:
                return int_conv2d_f32(x_q, qop.w_q, stride=op.stride)
            if op.kind == G.CONV1D:
                return int_conv1d_f32(x_q, qop.w_q, stride=op.stride)
            return int_pointwise_f32(x_q, qop.w_kern)
        raise ValueError(f"unknown tuned route {route!r} for {op.name}")
    if isinstance(qop, PreparedQOp):
        if op.kind == G.DW:
            return int_depthwise_shifts(x_q, qop.w_kern, stride=op.stride)
        if op.kind == G.DW1D:
            return int_depthwise1d_shifts(x_q, qop.w_kern, stride=op.stride)
        if op.kind in (G.PW, G.DENSE):
            if qop.f32_exact:
                return int_pointwise_f32(x_q, qop.w_kern)
            return int_pointwise(x_q, qop.w_kern)
        if op.kind == G.CONV:
            if qop.f32_exact:
                return int_conv2d_f32(x_q, qop.w_q, stride=op.stride)
            return int_conv2d(x_q, qop.w_q, stride=op.stride)
        if op.kind == G.CONV1D:
            if qop.f32_exact:
                return int_conv1d_f32(x_q, qop.w_q, stride=op.stride)
            return int_conv1d(x_q, qop.w_q, stride=op.stride)
        raise ValueError(op.kind)
    w_q = jnp.asarray(qop.w_q, jnp.int32)
    if op.kind == G.CONV:
        return int_conv2d(x_q, w_q, stride=op.stride)
    if op.kind == G.DW:
        return int_conv2d(x_q, w_q, stride=op.stride, groups=op.in_ch)
    if op.kind == G.CONV1D:
        return int_conv1d(x_q, w_q, stride=op.stride)
    if op.kind == G.DW1D:
        return int_conv1d(x_q, w_q, stride=op.stride, groups=op.in_ch)
    if op.kind == G.PW:
        return int_pointwise(x_q, w_q[0, 0] if w_q.ndim == 4 else w_q)
    if op.kind == G.DENSE:
        return int_pointwise(x_q, w_q)
    raise ValueError(op.kind)


def _run_qop(x_q: jnp.ndarray, qop, fixed_point: bool,
             route: Optional[Tuple[str, Dict[str, int]]] = None,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    op = qop.spec
    if route is not None and op.act != G.HSIGMOID and not fixed_point:
        name, params = route
        if name in ("pallas_pw", "pallas_dw"):
            # deferred import: kernels.ops imports this module at top level
            from repro.kernels import ops as K
            if name == "pallas_dw":
                return K.run_dw_qop(x_q, qop, interpret=interpret, **params)
            return K.run_pw_qop(x_q, qop, interpret=interpret, **params)
        acc = _accumulate(x_q, qop, route=name)
    else:
        acc = _accumulate(x_q, qop)

    if op.act == G.HSIGMOID:
        # gate: y = relu6(x + 3)/6 quantized to [0, qmax] with S=1/qmax.
        # dequant the accumulator (S_x*S_w), apply hsigmoid, requantize.
        zterm = _rounded(qop.in_zp * jnp.asarray(qop.wsum, jnp.float32))
        y_fp = (acc.astype(jnp.float32) + zterm) * (
            qop.in_scale * jnp.asarray(qop.w_scale, jnp.float32))
        y_fp, b_fp = _rounded(
            y_fp, jnp.asarray(qop.bias_q, jnp.float32) * qop.out_scale)
        y_fp = y_fp + b_fp
        # requantize with ONE constant multiply: chaining /6.0 with
        # /out_scale lets XLA reassociate the two divisions under jit
        # (reciprocal-multiply rewrites), flipping round() on boundary
        # values — jitted stage executors would drift off the eager
        # reference by 1 LSB. The f64-folded constant is order-free.
        requant = jnp.float32(1.0 / (6.0 * qop.out_scale))
        gate6 = jnp.clip(y_fp + 3.0, 0.0, 6.0)
        return jnp.round(gate6 * requant).astype(jnp.int32)

    if isinstance(qop, PreparedQOp):
        z_x, wsum = qop.z_x, qop.wsum
        bias, mult = qop.bias_q, qop.mult
        mantissa = qop.mantissa if fixed_point else None
        shift = qop.shift if fixed_point else None
    else:
        z_x = jnp.asarray(qop.in_zp, jnp.int32)
        wsum = jnp.asarray(qop.wsum, jnp.int32)
        bias = jnp.asarray(qop.bias_q, jnp.int32)
        mult = jnp.asarray(qop.mult, jnp.float32)
        mantissa = jnp.asarray(qop.mantissa, jnp.int64) if fixed_point else None
        shift = jnp.asarray(qop.shift, jnp.int32) if fixed_point else None
    return quantized_op_epilogue(
        acc,
        z_x=z_x,
        wsum=wsum,
        bias_q=bias,
        mult=mult,
        qmax=qop.qmax,
        z_y=jnp.asarray(0, jnp.int32),  # z_y folded into bias_q (qnet.py)
        fixed_point=fixed_point,
        mantissa=mantissa,
        shift=shift,
        clip_output=True,
    )


def _residual_add(
    a_q, a_s, a_z, b_q, b_s, b_z, y_s, y_z, qmax: int,
    fixed_consts=None,
) -> jnp.ndarray:
    """Skip-line add: rescale both operands into the output domain.

    Float-multiplier mode rescales in f32 (matching the requant multiplier's
    float mode). When `fixed_consts` is given (fixed_point mode), the add is
    pure integer: mantissa multiplies + one shared round-shift, the same
    'Approximator' arithmetic as the per-op fixed-point requant — no float
    remains anywhere in the fixed-point datapath.
    """
    if fixed_consts is not None:
        return int_residual_add(a_q, b_q, fixed_consts, qmax)
    a, b = _rounded((a_q.astype(jnp.float32) + a_z) * (a_s / y_s),
                    (b_q.astype(jnp.float32) + b_z) * (b_s / y_s))
    return jnp.clip(jnp.round(a + b) - round(y_z), 0, qmax).astype(jnp.int32)


def _residual_consts_for(block, qnet, a_s, a_z, b_s, b_z, y_s, y_z):
    """Integer skip-add constants: cached on a PreparedQNet, else derived."""
    if isinstance(qnet, PreparedQNet):
        return qnet.res_fixed[block.name]
    return residual_fixed_consts(a_s, a_z, b_s, b_z, y_s, y_z)


def run_block(
    x_q: jnp.ndarray,
    block: G.BlockSpec,
    qnet: Union[QNet, PreparedQNet],
    in_s: float,
    in_z: float,
    fixed_point: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, float, float]:
    """Execute one block (one CU invocation) fully fused in integer math.

    A `PreparedQNet` carrying tuned `routes` (see `prepare_qnet(tuned=)`)
    dispatches each routed op through its measured route; everything else
    takes the default formulation. Tuned routes are float-requant only, so
    `fixed_point=True` ignores them (the reference fixed-point datapath is
    the bit-exactness contract there). `interpret` forwards to any routed
    Pallas kernel (None = auto by backend)."""
    routes = None
    if not fixed_point and isinstance(qnet, PreparedQNet) and qnet.routes:
        routes = qnet.routes
    y = x_q
    cur_s, cur_z = in_s, in_z
    for op in block.ops:
        qop = qnet.ops[op.name]
        y = _run_qop(y, qop, fixed_point,
                     route=routes.get(op.name) if routes else None,
                     interpret=interpret)
        cur_s, cur_z = qop.out_scale, qop.out_zp
        if block.se is not None and block.se_after == op.name:
            sq, ex = qnet.ops[block.se.squeeze.name], qnet.ops[block.se.excite.name]
            sp_axes = tuple(range(1, y.ndim - 1))  # (1, 2) NHWC / (1,) NTC
            pooled = jnp.round(jnp.mean(y.astype(jnp.float32), axis=sp_axes)).astype(jnp.int32)
            s = _run_qop(pooled, sq, fixed_point)
            gate_q = _run_qop(s, ex, fixed_point)  # [B, C] in [0, qmax], S=1/qmax
            # gated output keeps the dw quantizer: y' = y * gate
            # S_y (y'_q + z) = S_y (y_q + z) * S_g * g_q  with z == 0 (ReLU6 fused)
            gate_b = gate_q.reshape(
                gate_q.shape[0], *([1] * len(sp_axes)), gate_q.shape[-1])
            y = jnp.round(
                y.astype(jnp.float32)
                * gate_b.astype(jnp.float32)
                * ex.out_scale
            ).astype(jnp.int32)
    if block.residual:
        y_s, y_z = qnet.res_q[block.name]
        qmax = 2 ** block.ops[-1].act_bits - 1
        fixed_consts = None
        if fixed_point:
            fixed_consts = _residual_consts_for(
                block, qnet, in_s, in_z, cur_s, cur_z, y_s, y_z)
        y = _residual_add(x_q, in_s, in_z, y, cur_s, cur_z, y_s, y_z, qmax,
                          fixed_consts=fixed_consts)
        cur_s, cur_z = y_s, y_z
    if block.avgpool:
        sp_axes = tuple(range(1, y.ndim - 1))  # (1, 2) NHWC / (1,) NTC
        y = jnp.round(jnp.mean(y.astype(jnp.float32), axis=sp_axes)).astype(jnp.int32)
    return y, cur_s, cur_z


def run_blocks(
    x_q: jnp.ndarray,
    blocks,
    qnet: Union[QNet, PreparedQNet],
    in_s: float,
    in_z: float,
    fixed_point: bool = False,
) -> Tuple[jnp.ndarray, float, float]:
    """Execute a contiguous block sequence (e.g. one CU stage's blocks)."""
    y, cur_s, cur_z = x_q, in_s, in_z
    for block in blocks:
        y, cur_s, cur_z = run_block(y, block, qnet, cur_s, cur_z, fixed_point)
    return y, cur_s, cur_z


def propagate_qparams(blocks, qnet: QNet, in_s: float, in_z: float):
    """(scale, zp) of the tensor leaving `blocks`, computed from QNet
    metadata only — no data needed. Matches `run_blocks` exactly, which is
    what lets the stage compiler bake per-stage quantizers in as statics."""
    cur_s, cur_z = in_s, in_z
    for block in blocks:
        for op in block.ops:
            qop = qnet.ops[op.name]
            cur_s, cur_z = qop.out_scale, qop.out_zp
        if block.residual:
            cur_s, cur_z = qnet.res_q[block.name]
    return cur_s, cur_z


def input_qparams(qnet: QNet) -> Tuple[float, float]:
    """The network input quantizer (the first op's input activation)."""
    first = qnet.ops[qnet.spec.blocks[0].ops[0].name]
    return first.in_scale, first.in_zp


def run_qnet(
    qnet: Union[QNet, PreparedQNet],
    x: jnp.ndarray,
    fixed_point: bool = False,
    input_bits: int = 8,
) -> jnp.ndarray:
    """Full integer inference. Returns float logits (dequantized at the end,
    where the FPGA hands confidence computation back to the PS/softmax).

    Pass a `PreparedQNet` (see `prepare_qnet`) to run the compiled integer
    fast path with zero per-call host->device constant uploads; the logits
    are bit-identical either way."""
    in_s, in_z = input_qparams(qnet)
    y = quantize_input(x, in_s, in_z, input_bits)
    y, cur_s, cur_z = run_blocks(y, qnet.spec.blocks, qnet, in_s, in_z,
                                 fixed_point)
    return (y.astype(jnp.float32) + cur_z) * cur_s


__all__ = [
    "quantize_input",
    "PreparedQOp",
    "PreparedQNet",
    "prepare_qnet",
    "replicate_prepared",
    "run_block",
    "run_blocks",
    "propagate_qparams",
    "input_qparams",
    "run_qnet",
]
