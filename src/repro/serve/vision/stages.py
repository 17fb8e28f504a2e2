"""Stage compiler: `CUPlan` schedule -> one jitted executor per CU role.

The FPGA runs each CU as fixed silicon reconfigured per invocation over
AXI-Lite; the XLA analogue is one jitted function per CU *stage* (the
contiguous run of same-role invocations in the schedule), traced once per
batch bucket. All intra-stage intermediates stay on-chip, exactly like the
FPGA's FIFO-streamed operator pipeline.

The integer datapath runs on one of three op implementations per stage:

  * prepared XLA fast path (default) — `cu.prepare_qnet` lowers the QNet to
    device-resident constants once at plan-build time, and the CU runners
    switch to the compiled integer formulations (shifted-slice depthwise,
    exactness-gated f32 matmul/conv). Bit-exact with the reference; this is
    what makes the hot loop fast off-TPU.
  * per-op Pallas kernels (`op_kernels`) — DW through the row-tiled
    depthwise kernel, PW/DENSE (Head/Body/Tail/Classifier) through the
    pointwise-CU kernel. "auto" enables them on a real TPU.
  * fused-IRB Pallas kernel (`body_fast_path`) — canonical Body blocks as
    one kernel that pins the t*C-expanded intermediate into VMEM.

Quantizer handoff between stages is static: `cu.propagate_qparams` derives
each stage's (scale, zp) contract from QNet metadata alone, so a stage
function is a pure array -> array map and the executor chain is bit-exact
with the monolithic `cu.run_qnet` reference. On accelerators, stage inputs
are donated at the stage boundary (`donate="auto"`): an intermediate
activation buffer is dead the moment the next stage consumes it, so XLA can
reuse it for the stage's own output instead of allocating fresh HBM.

The per-CU programs are the lowering unit (the autotuner and the tests run
them one by one). The serving engine runs `CompiledNet`: the same CU
computations chained inside ONE jitted program per batch bucket. On one
device the stage programs run one after another anyway, and every launch
costs host time whatever its device work, so one launch per micro-batch
replaces one per CU.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compiler as CC
from repro.dist.sharding import batch_sharding
from repro.core import cu
from repro.core import graph as G
from repro.core.qnet import QNet
from repro.kernels import ops as K


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """Everything needed to (re)trace one CU stage executor."""

    cu: str
    blocks: Tuple[G.BlockSpec, ...]
    in_scale: float
    in_zp: float
    out_scale: float
    out_zp: float
    quantizes_input: bool  # Head: float image -> int activations
    dequantizes_output: bool  # Classifier: int logits -> float logits
    signature: Optional[CC.StageSignature]  # None for the whole network


class CompiledStage:
    """One CU stage as a jitted callable.

    Batch-polymorphic by bucketing: jax retraces per input shape, and the
    engine only ever presents bucket-padded batches, so the trace cache
    stays one entry per (stage, bucket)."""

    def __init__(self, spec: StageSpec, qnet: Union[QNet, cu.PreparedQNet],
                 *, fixed_point: bool, input_bits: int, fast_path: bool,
                 op_kernels: bool, interpret: Optional[bool],
                 donate: bool = False, mesh=None, tuned: bool = False,
                 fused_blocks: frozenset = frozenset()):
        self.spec = spec
        self._qnet = qnet
        self._fixed_point = fixed_point
        self._input_bits = input_bits
        self._fast_path = fast_path and spec.cu == CC.BODY
        self._op_kernels = op_kernels
        self._interpret = interpret
        self._tuned = tuned
        self._fused_blocks = fused_blocks
        self._jit(donate=donate, mesh=mesh)

    def _jit(self, *, donate: bool, mesh) -> None:
        """Counters, retrace-leak detection, and the jitted program `_fn`."""
        self.mesh = mesh
        self.invocations = 0  # CU invocations dispatched (micro-batches)
        self.traces = 0  # jit cache misses (should stay == #buckets)
        # retrace-leak detection: the engine pins the batch sizes it may
        # legally present (its buckets); a trace at any other leading dim
        # is a leak — some caller slipped a non-bucketed shape through and
        # is silently paying an XLA retrace per novel shape.
        self.allowed_batches: Optional[frozenset] = None
        self.retraces = 0  # traces outside `allowed_batches`
        self.on_retrace: Optional[Callable[["CompiledStage", Tuple[int, ...]],
                                           None]] = None
        jit_kwargs = dict(donate_argnums=(0,) if donate else ())
        self._body = self._compute
        if mesh is not None:
            # data-parallel replication: micro-batch rows split along the
            # mesh 'data' axis in AND out, so an N-replica mesh runs N
            # shards of every CU invocation concurrently. Constants are
            # replicated (prepare_qnet(mesh=...)), activations stay sharded
            # across the whole executor chain — no resharding between CUs.
            # Rows are independent, so each replica runs the stage on its
            # own rows under shard_map: the compiler cannot partition a
            # Pallas kernel itself (and pallas_call declares no per-axis
            # variance, hence check_vma=False).
            ns = batch_sharding(mesh)
            jit_kwargs.update(in_shardings=ns, out_shardings=ns)
            self._body = jax.shard_map(
                self._compute, mesh=mesh, in_specs=P("data"),
                out_specs=P("data"), check_vma=False)
        self._fn = jax.jit(self._named(), **jit_kwargs)

    def _named(self) -> Callable[[jax.Array], jax.Array]:
        """`_trace` as a function named `stage_<cu>`: the program is then
        the XLA module `jit_stage_<cu>`. `_compute` puts each CU's ops
        under the scope `<cu>/`; kernel names stay those of the kernels."""

        def stage(x: jax.Array) -> jax.Array:
            return self._trace(x)

        stage.__name__ = stage.__qualname__ = f"stage_{self.spec.cu}"
        return stage

    def _trace(self, x: jax.Array) -> jax.Array:
        self.traces += 1
        if (self.allowed_batches is not None
                and x.shape[0] not in self.allowed_batches):
            # a retrace leak, not an error: serving stays correct (jax just
            # traces again), but every novel shape pays a fresh compile on
            # the hot path — surface it loudly instead of hiding the stall
            self.retraces += 1
            warnings.warn(
                f"stage {self.spec.cu}: retrace at non-bucketed batch "
                f"shape {tuple(x.shape)} (buckets "
                f"{sorted(self.allowed_batches)}) — a caller bypassed the "
                f"batch former; every novel shape recompiles this stage",
                RuntimeWarning, stacklevel=2)
            if self.on_retrace is not None:
                self.on_retrace(self, tuple(x.shape))
        return self._body(x)

    def _compute(self, x: jax.Array) -> jax.Array:
        with jax.named_scope(self.spec.cu):
            return self._run_blocks(x)

    def _run_blocks(self, x: jax.Array) -> jax.Array:
        spec = self.spec
        y = x
        if spec.quantizes_input:
            y = cu.quantize_input(
                y, spec.in_scale, spec.in_zp, self._input_bits)
        s, z = spec.in_scale, spec.in_zp
        for block in spec.blocks:
            if self._tuned:
                # measured route selection: the TunedPlan's per-op routes
                # ride on the PreparedQNet (cu.run_block dispatches them);
                # fused-IRB block choices are honored here. Ops/blocks
                # without a cache entry fall back to the default route.
                if block.name in self._fused_blocks and K.fusable_irb(block):
                    y, s, z = K.run_irb_block(
                        y, block, self._qnet, s, z,
                        interpret=self._interpret)
                else:
                    y, s, z = cu.run_block(
                        y, block, self._qnet, s, z, self._fixed_point,
                        interpret=self._interpret)
            elif self._fast_path and K.fusable_irb(block):
                y, s, z = K.run_irb_block(
                    y, block, self._qnet, s, z, interpret=self._interpret)
            elif self._op_kernels:
                y, s, z = K.run_block_kernels(
                    y, block, self._qnet, s, z, interpret=self._interpret)
            else:
                y, s, z = cu.run_block(
                    y, block, self._qnet, s, z, self._fixed_point)
        if spec.dequantizes_output:
            y = (y.astype(jnp.float32) + z) * s
        return y

    def __call__(self, x: jax.Array) -> jax.Array:
        self.invocations += 1
        return self._fn(x)


class CompiledNet(CompiledStage):
    """The whole CU chain as ONE jitted program per batch bucket.

    Runs each stage's computation in order inside `stage_net` (XLA module
    `jit_stage_net`); every CU keeps its `jax.named_scope`, so op metadata
    still reads `head/` ... `classifier/`. The (scale, zp) handoff, tuned
    routes and fused-block choices are the stages' own; activations cross
    the old stage boundaries inside the program, so there is nothing to
    donate. Under a mesh one `shard_map` wraps the whole chain. Its spec
    spans the chain: the input contract of the first stage, the output
    contract of the last."""

    def __init__(self, stages: Sequence[CompiledStage]):
        if not stages:
            raise ValueError("need at least one stage")
        self.stages = tuple(stages)
        first, last = self.stages[0].spec, self.stages[-1].spec
        self.spec = StageSpec(
            cu="net",
            blocks=tuple(b for st in self.stages for b in st.spec.blocks),
            in_scale=first.in_scale,
            in_zp=first.in_zp,
            out_scale=last.out_scale,
            out_zp=last.out_zp,
            quantizes_input=first.quantizes_input,
            dequantizes_output=last.dequantizes_output,
            signature=None,
        )
        self._jit(donate=False, mesh=self.stages[0].mesh)

    def _compute(self, x: jax.Array) -> jax.Array:
        for st in self.stages:
            x = st._compute(x)
        return x


def _resolve(flag: str, name: str) -> bool:
    if flag not in ("auto", "on", "off"):
        raise ValueError(f"{name}={flag!r}")
    return K.on_tpu() if flag == "auto" else flag == "on"


def compile_stages(
    qnet: Union[QNet, cu.PreparedQNet],
    plan: Optional[CC.CUPlan] = None,
    *,
    fixed_point: bool = False,
    input_bits: int = 8,
    body_fast_path: str = "auto",  # "auto" | "on" | "off"
    op_kernels: str = "auto",  # "auto" | "on" | "off"
    prepare: bool = True,
    donate: str = "auto",  # "auto" | "on" | "off"
    interpret: Optional[bool] = None,
    mesh=None,
    tuned=None,
) -> List[CompiledStage]:
    """Lower a CUPlan into the ordered list of jitted stage executors.

    `body_fast_path`: route fusable Body blocks through the Pallas fused-IRB
    kernel. `op_kernels`: route DW/PW/DENSE ops through the per-op Pallas
    kernels in every stage. Both are "auto" == only on a real TPU (in
    interpret mode the kernels are emulated and slower than the compiled XLA
    path, though still bit-exact); "on"/"off" force either way.

    `tuned` (a `repro.tune.TunedPlan`, or carried on `plan.tuned`) REPLACES
    those hard-coded heuristics with measured cache lookup: each op runs the
    route the autotuner verified bit-exact and timed fastest for its
    (kind, shape, act_bits, backend) key; fusable Body blocks honor the
    block-level fused-IRB decision. Ops/blocks with no cache entry fall
    back to today's defaults, so a partial or foreign-backend cache is
    always safe. Tuned routes are float-requant formulations, so
    `fixed_point=True` is refused, and routes bind to prepared constants,
    so `prepare=False` is refused too.

    `prepare`: lower the QNet with `cu.prepare_qnet` first (device-resident
    constants + compiled integer formulations). Default on — this is the
    serving configuration; "off" reproduces the PR-1 reference stages.

    `donate`: donate each non-Head stage's input buffer to XLA ("auto" ==
    only on accelerator backends; the CPU runtime cannot reuse donations
    and would warn).

    `mesh`: a 1-D 'data' mesh (`dist.sharding.data_mesh`) replicates the
    whole executor chain: constants replicated on every device, micro-batch
    rows sharded along 'data' in and out of every stage. Batch sizes must
    divide by the replica count. `None` (default) is the single-device
    configuration, byte-identical to previous behavior.
    """
    if mesh is not None and "data" not in mesh.axis_names:
        raise ValueError(f"mesh needs a 'data' axis, got {mesh.axis_names}")
    if plan is None:
        plan = CC.compile_net(qnet.spec)
    if tuned is None:
        tuned = getattr(plan, "tuned", None)
    fused_blocks: frozenset = frozenset()
    fast = _resolve(body_fast_path, "body_fast_path")
    kerns = _resolve(op_kernels, "op_kernels")
    op_routes = None
    if tuned is not None:
        if fixed_point:
            raise ValueError(
                "tuned= carries float-requant routes only and cannot "
                "serve fixed_point=True")
        if not prepare:
            raise ValueError(
                "tuned= requires prepare=True (routes bind to PreparedQOp "
                "device constants)")
        # one resolve, with cache MISSES filled by the heuristic defaults
        # (on TPU an uncovered op keeps the default-tile Pallas route, an
        # uncovered fusable block keeps the fused kernel) — a partial or
        # foreign-backend cache can never silently degrade a route below
        # what the non-tuned heuristics would run
        op_routes, fused = tuned.resolve_with_defaults(
            qnet, plan, op_kernels=kerns, body_fast_path=fast)
        if not op_routes and not fused:
            tuned = op_routes = None  # nothing to route: pure heuristics
        fused_blocks = frozenset(fused or ())
    if fixed_point and (fast or kerns):
        # the Pallas kernels' requant epilogue is float-multiplier only; a
        # silent fallback would break bit-exactness with
        # run_qnet(fixed_point=True)
        if body_fast_path == "on" or op_kernels == "on":
            raise ValueError(
                "body_fast_path/op_kernels='on' is incompatible with "
                "fixed_point=True (the Pallas kernels have no fixed-point "
                "requant mode)")
        fast = kerns = False
    if donate not in ("auto", "on", "off"):
        raise ValueError(f"donate={donate!r}")
    donate_ok = (jax.default_backend() != "cpu") if donate == "auto" \
        else donate == "on"
    if prepare:
        qnet = cu.prepare_qnet(qnet, input_bits=input_bits, mesh=mesh,
                               routes=op_routes)
    elif mesh is not None and isinstance(qnet, cu.PreparedQNet):
        qnet = cu.replicate_prepared(qnet, mesh)

    sigs = plan.stage_signatures()
    stages: List[CompiledStage] = []
    s, z = cu.input_qparams(qnet)
    for i, sig in enumerate(sigs):
        out_s, out_z = cu.propagate_qparams(sig.blocks, qnet, s, z)
        spec = StageSpec(
            cu=sig.cu,
            blocks=sig.blocks,
            in_scale=s,
            in_zp=z,
            out_scale=out_s,
            out_zp=out_z,
            quantizes_input=(i == 0),
            dequantizes_output=(i == len(sigs) - 1),
            signature=sig,
        )
        stages.append(CompiledStage(
            spec, qnet, fixed_point=fixed_point, input_bits=input_bits,
            fast_path=fast, op_kernels=kerns, interpret=interpret,
            donate=donate_ok and i > 0, mesh=mesh,
            tuned=tuned is not None, fused_blocks=fused_blocks))
        s, z = out_s, out_z
    return stages


__all__ = ["StageSpec", "CompiledStage", "CompiledNet", "compile_stages"]
