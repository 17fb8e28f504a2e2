"""Software-pipelined scheduler over the CU stage executors.

The paper's host double-buffers CU invocations: while the Body CU crunches
micro-batch k, the Head CU already streams micro-batch k+1 out of DDR. On
XLA the same overlap falls out of asynchronous dispatch — every program
call returns a future-backed Array immediately — provided the caller *keeps
several micro-batches in flight* instead of blocking batch-by-batch.

One scheduler tick (`advance`) moves every occupied slot one stage on
(walking stages back-to-front, so a micro-batch moves exactly one stage per
tick); `inject` then puts the next micro-batch in the first slot. All
dispatches inside a tick are enqueued without synchronisation; the only
blocking point is harvesting a finished output. A batch that leaves the
last stage is returned once `WINDOW` micro-batches are in flight, or at
once when the tick launched nothing new (the flush at the end of a drain).
With several stages a finished batch always meets one of the two, so it
comes back the tick it leaves. With one program (the engine's
`CompiledNet`) batch k is returned only after batch k+1 is launched: the
device runs k+1 while the host harvests and records k and forms k+2.

Observability (`tracer=` / `metrics=`, see `repro.obs`): each program
dispatch becomes a span `dispatch:<cu>` on that program's trace track
(dispatch/enqueue time — XLA dispatch is asynchronous, so *compute* shows
up as harvest wait at the sync point, which is also traced), plus
per-program dispatch-seconds instruments and a harvest-wait histogram;
dispatch and harvest spans carry the micro-batch's `batch` id when the
caller installs `batch_id`. All extra clock reads are guarded by
`if tracer` / registered-instrument no-ops: with observability off the
executor performs exactly the clock reads it always did (fake-clock tests
stay bitwise).
"""
from __future__ import annotations

import collections
import time
from typing import (Any, Callable, Deque, Dict, Iterable, Iterator, List,
                    Optional, Tuple)

import jax

from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.serve.vision.stages import CompiledStage

# micro-batches in flight behind the last program. Fixed: one more than the
# device can run buys the host/device overlap, and a wider window only adds
# latency while the host is the slower side.
WINDOW = 2


class PipelinedExecutor:
    def __init__(self, stages: List[CompiledStage], clock=None,
                 tracer: Optional[OT.Tracer] = None, metrics=None):
        if not stages:
            raise ValueError("need at least one stage")
        self.stages = stages
        self._slots: List[Optional[Tuple[Any, jax.Array]]] = \
            [None] * len(stages)
        # outputs of the last stage, dispatched but not yet returned
        self._done: Deque[Tuple[Any, jax.Array]] = collections.deque()
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
        return bool(self._done) or any(s is not None for s in self._slots)

    # -- tick-level API (used directly by the multi-model router) ----------

    def advance(self) -> Optional[Tuple[Any, jax.Array]]:
        """One scheduler tick: every occupied slot advances exactly one
        stage (back-to-front, all dispatches async). Frees the first slot.
        Returns the oldest (tag, y) out of the last stage once `WINDOW`
        micro-batches are in flight, or at once when nothing new was
        injected (flush), else None — NOT yet blocked on; callers harvest
        via `harvest`."""
        self._m_ticks.inc()
        launched = self._slots[0] is not None
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
                self._done.append((tag, y))
        in_flight = len(self._done) + sum(s is not None for s in self._slots)
        if self._done and (not launched or in_flight >= WINDOW):
            return self._done.popleft()
        return None

    def inject(self, batch: Tuple[Any, jax.Array]) -> None:
        """Occupy the first slot with the next micro-batch."""
        if self._slots[0] is not None:
            raise RuntimeError("first slot occupied — advance() first")
        self._slots[0] = batch

    def reset(self) -> None:
        """Drop every in-flight micro-batch (abandoned drain): a later
        stream()/run() must never replay stale tags into its results."""
        self._slots = [None] * self.depth
        self._done.clear()

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
