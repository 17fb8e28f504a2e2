"""Software-pipelined scheduler over the CU stage executors.

The paper's host double-buffers CU invocations: while the Body CU crunches
micro-batch k, the Head CU already streams micro-batch k+1 out of DDR. On
XLA the same overlap falls out of asynchronous dispatch — every stage call
returns a future-backed Array immediately — provided the driver *keeps
multiple micro-batches in flight* instead of blocking batch-by-batch.

`PipelinedExecutor.stream` does exactly that: one scheduler tick advances
every occupied pipeline slot by one stage (walking stages back-to-front so
a micro-batch moves exactly one stage per tick) and then injects the next
micro-batch into the Head slot. All dispatches inside a tick are enqueued
without synchronisation; the only blocking point is harvesting a finished
Classifier output, by which time the ticks have already queued Head/Body
work for the following micro-batches.

Observability (`tracer=` / `metrics=`, see `repro.obs`): each stage
dispatch becomes a span on that CU's trace track (dispatch/enqueue time —
XLA dispatch is asynchronous, so stage *compute* shows up as harvest wait
at the sync point, which is also traced), plus per-stage dispatch-seconds
instruments and a harvest-wait histogram; dispatch and harvest spans carry
the micro-batch's `batch` id when the caller installs `batch_id`. All extra
clock reads are guarded by `if tracer` / registered-instrument no-ops: with
observability off the executor performs exactly the clock reads it always
did (fake-clock tests stay bitwise).
"""
from __future__ import annotations

import time
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    Optional, Tuple)

import jax

from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.serve.vision.stages import CompiledStage


class PipelinedExecutor:
    def __init__(self, stages: List[CompiledStage], clock=None,
                 tracer: Optional[OT.Tracer] = None, metrics=None):
        if not stages:
            raise ValueError("need at least one stage")
        self.stages = stages
        self._slots: List[Optional[Tuple[Any, jax.Array]]] = \
            [None] * len(stages)
        # harvest_wait_s reads the same injectable clock as the engine —
        # one time source for every stat (see VisionEngine's clock)
        self._clock = time.perf_counter if clock is None else clock
        self._streaming = False
        # wall time spent blocked on finished outputs (pipeline stall proxy)
        self.harvest_wait_s = 0.0
        self.tracer = tracer if tracer is not None else OT.NULL
        # optional tag -> micro-batch id hook: the engine installs one so
        # every dispatch and harvest span names the batch it served (the
        # engine's form_batch span ties that id to request ids)
        self.batch_id: Optional[Callable[[Any], int]] = None
        reg = metrics if metrics is not None else OM.NULL_REGISTRY
        self._m_harvest = reg.histogram(
            "serve_harvest_wait_seconds",
            "wall time blocked on a finished stage output (the pipeline's "
            "only sync point)")
        self._m_ticks = reg.counter(
            "serve_pipeline_ticks_total", "scheduler ticks advanced")
        self._m_stage_dispatch = []
        for i, stage in enumerate(stages):
            cu = stage.spec.cu
            self._m_stage_dispatch.append(reg.histogram(
                "serve_stage_dispatch_seconds",
                "per-stage dispatch (enqueue) wall time", labels={"cu": cu}))
            if self.tracer:
                self.tracer.name_track(OT.TID_STAGE0 + i, f"stage:{cu}")

    @property
    def depth(self) -> int:
        return len(self.stages)

    @property
    def busy(self) -> bool:
        """True while any micro-batch is still in flight."""
        return any(s is not None for s in self._slots)

    # -- tick-level API (used directly by the multi-model router) ----------

    def advance(self) -> Optional[Tuple[Any, jax.Array]]:
        """One scheduler tick: every occupied slot advances exactly one
        stage (back-to-front, all dispatches async). Frees the Head slot.
        Returns the (tag, y) that left the last stage this tick, if any —
        NOT yet blocked on; callers harvest via `harvest`."""
        finished = None
        self._m_ticks.inc()
        for i in reversed(range(self.depth)):
            if self._slots[i] is None:
                continue
            tag, x = self._slots[i]
            self._slots[i] = None
            if self.tracer:
                t0 = self._clock()
                y = self.stages[i](x)  # async dispatch — returns immediately
                t1 = self._clock()
                self.tracer.complete(
                    f"dispatch:{self.stages[i].spec.cu}", t0, t1,
                    cat="stage", tid=OT.TID_STAGE0 + i,
                    args=self._span_args(tag, rows=int(x.shape[0])))
                self._m_stage_dispatch[i].observe(t1 - t0)
            else:
                y = self.stages[i](x)  # async dispatch — returns immediately
            if i + 1 < self.depth:
                self._slots[i + 1] = (tag, y)
            else:
                finished = (tag, y)
        return finished

    def inject(self, batch: Tuple[Any, jax.Array]) -> None:
        """Occupy the Head slot with the next micro-batch."""
        if self._slots[0] is not None:
            raise RuntimeError("Head slot occupied — advance() first")
        self._slots[0] = batch

    def reset(self) -> None:
        """Drop every in-flight micro-batch (abandoned drain): a later
        stream()/run() must never replay stale tags into its results."""
        self._slots = [None] * self.depth

    def harvest(self, finished: Tuple[Any, jax.Array]) -> Tuple[Any, jax.Array]:
        """Block until a finished output is ready (the only sync point)."""
        t0 = self._clock()
        jax.block_until_ready(finished[1])
        t1 = self._clock()
        self.harvest_wait_s += t1 - t0
        self._m_harvest.observe(t1 - t0)
        if self.tracer:
            self.tracer.complete("harvest", t0, t1, cat="pipeline",
                                 tid=OT.TID_SCHED,
                                 args=self._span_args(finished[0]))
        return finished

    def _span_args(self, tag: Any, **args: Any) -> Dict[str, Any]:
        if self.batch_id is not None:
            args["batch"] = self.batch_id(tag)
        return args

    # -- streaming driver ---------------------------------------------------

    def stream(
        self, batches: Iterable[Tuple[Any, jax.Array]],
    ) -> Iterator[Tuple[Any, jax.Array]]:
        """Stream (tag, x) micro-batches through the stages; yield
        (tag, y) in completion order (== submission order: the pipeline
        is in-order). Outputs are harvested ready — iterating does not
        add synchronisation beyond the final stage itself."""
        if self._streaming or self.busy:
            raise RuntimeError(
                "PipelinedExecutor is already draining — one stream() (or "
                "tick-level drive) at a time")
        self._streaming = True
        it = iter(batches)
        exhausted = False
        try:
            while True:
                finished = self.advance()
                if not exhausted:
                    try:
                        self.inject(next(it))
                    except StopIteration:
                        exhausted = True
                if finished is not None:
                    yield self.harvest(finished)
                if exhausted and not self.busy:
                    return
        finally:
            # abandoned mid-drain (caller broke out / exception): slots
            # used to be local per call; instance slots must be cleared to
            # keep that contract
            self._streaming = False
            self.reset()

    def run(self, batches: Iterable[jax.Array]) -> List[jax.Array]:
        """Convenience: pipeline a list of micro-batches, return outputs."""
        tagged = ((i, x) for i, x in enumerate(batches))
        return [y for _, y in self.stream(tagged)]

    def warmup(self, example: jax.Array) -> None:
        """Trace every stage at `example`'s batch size (one bucket).

        Bypasses `__call__` so warmup traces don't count as CU
        invocations in the serving stats. With tracing on, each stage is
        blocked on before the next — the one place per-stage *compute*
        wall time is observable without breaking pipelining, so the spans
        land on the stage tracks as `warmup:{cu}`."""
        x = example
        for i, stage in enumerate(self.stages):
            if self.tracer:
                t0 = self._clock()
                x = jax.block_until_ready(stage._fn(x))
                self.tracer.complete(
                    f"warmup:{stage.spec.cu}", t0, self._clock(),
                    cat="stage", tid=OT.TID_STAGE0 + i,
                    args={"rows": int(example.shape[0])})
            else:
                x = stage._fn(x)
        jax.block_until_ready(x)


__all__ = ["PipelinedExecutor"]
