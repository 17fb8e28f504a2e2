"""Vision serving subsystem: continuous-batching integer DSCNN inference
over pipelined CU stages (paper Sec. 4's CU invocation schedule, serving
form).

Layers, bottom-up:

  * `stages`   — the stage compiler: lowers a `CUPlan` schedule into one
                 jitted, bucket-batched executor per CU role
                 (Head / Body / Tail / Classifier), and chains them into
                 the one program per bucket the engine serves
                 (`CompiledNet`).
  * `pipeline` — the scheduler: streams micro-batches through the
                 programs with two in flight (the paper's double-buffered
                 CU invocation schedule).
  * `engine`   — the continuous-batching front end: request queue, dynamic
                 batch former with shape/bucket admission, per-request
                 deadlines, and throughput/latency/energy-proxy stats
                 (the Table 6 FPS / FPS-per-Watt view). `mesh=` shards
                 micro-batches data-parallel across a `dist.sharding`
                 'data' mesh (constants replicated, bit-exact); the
                 `MultiModelEngine` router serves several models through
                 per-model pipelines under one EDF dispatch policy.
"""
from repro.serve.vision.engine import (
    AdmissionError,
    EngineStats,
    MultiModelEngine,
    RequestResult,
    VisionEngine,
    VisionRequest,
)
from repro.serve.vision.pipeline import PipelinedExecutor
from repro.serve.vision.stages import (CompiledNet, CompiledStage,
                                      compile_stages)

__all__ = [
    "AdmissionError",
    "CompiledNet",
    "CompiledStage",
    "EngineStats",
    "MultiModelEngine",
    "PipelinedExecutor",
    "RequestResult",
    "VisionEngine",
    "VisionRequest",
    "compile_stages",
]
