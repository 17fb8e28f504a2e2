"""Quickstart: serve a quantized MobileNet-V2 through the pipelined
CU-stage vision engine.

    PYTHONPATH=src python examples/serve_vision.py

Multi-replica sharded serving (split micro-batches across N CPU devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python examples/serve_vision.py

Walks the full deployment path from the paper: build the NetSpec, calibrate
activations, quantize to an integer QNet, compile the CU schedule into
stage executors chained into one program per batch bucket, then serve a
stream of requests with continuous batching —
and shows the engine output is bit-exact with the reference integer runner.
When more than one device is visible, the engine shards every micro-batch
data-parallel across a `dist.sharding.data_mesh`; the logits stay
bit-identical to the single-device run. A second model (the compact
EfficientNet) is served concurrently through the EDF `MultiModelEngine`.
The multi-model run records a request-lifecycle trace + metrics
(`repro.obs`), dumps the trace as Perfetto-loadable Chrome JSON, and prints
the pipeline-profile summary.
"""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler as CC, cu
from repro.dist.sharding import data_mesh
from repro.models import efficientnet as effn, mobilenet_v2 as mnv2
from repro.models.layers import make_calibrated_qnet
from repro.obs import MetricsRegistry, Tracer, render_report, summarize_trace
from repro.serve.vision import MultiModelEngine, VisionEngine


def main():
    hw = 64
    net = mnv2.build(alpha=0.35, input_hw=hw, num_classes=1000)
    # front-end: float model -> calibrated integer QNet (BW=4)
    qnet = make_calibrated_qnet(net, n_cal=4)

    # back-end: CU schedule -> pipelined serving engine, replicated over
    # every visible device (a single device degenerates to the plain engine)
    plan = CC.compile_net(net)
    print("CU schedule:", [(s.cu, s.invocations)
                           for s in plan.stage_signatures()])
    n_dev = len(jax.devices())
    mesh = data_mesh(n_dev) if n_dev > 1 else None
    print(f"serving over {n_dev} device(s)"
          + (f" (mesh {dict(mesh.shape)})" if mesh else ""))
    engine = VisionEngine(qnet, plan, buckets=(1, 2, 4, 8), mesh=mesh)
    engine.warmup()

    # 3. serve a request stream (some with deadlines)
    images = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(7), (20, hw, hw, 3), minval=-1, maxval=1))
    now = time.perf_counter()
    rids = []
    for i, img in enumerate(images):
        deadline = now + 5.0 if i % 3 == 0 else None
        rids.append(engine.submit(img, deadline_s=deadline))
    results = engine.run()

    # 4. check against the monolithic integer reference + report stats
    ref = np.asarray(cu.run_qnet(qnet, jnp.asarray(images)))
    got = np.stack([results[r].logits for r in rids])
    print("bit-exact with cu.run_qnet:", bool(np.array_equal(got, ref)))
    stats = engine.stats()
    print(f"served {stats.n_ok} images in {stats.wall_s:.3f}s "
          f"({stats.fps:.1f} FPS, p95 latency {stats.latency_p95_s*1e3:.0f}ms)")
    print(f"micro-batches: {stats.micro_batches} "
          f"(pad fraction {stats.pad_fraction:.2f}), "
          f"stage invocations: {stats.stage_invocations}")
    print(f"modeled energy: {stats.energy_j_per_image*1e6:.2f} uJ/image "
          f"({stats.power_source}, {stats.energy_tuned_fraction:.0%} from "
          f"measured routes) -> {stats.watts:.1f} W, "
          f"{stats.fps_per_watt:.1f} FPS/W")

    # 5. multi-model routing: MobileNetV2 + compact EfficientNet share the
    # device(s); the router dispatches micro-batches EDF across models.
    # One shared tracer + registry puts both models on one observability
    # timeline (per-request lifecycle spans, per-stage dispatch tracks).
    tracer, metrics = Tracer(), MetricsRegistry()
    effq = make_calibrated_qnet(
        effn.build_compact(input_hw=hw, num_classes=1000), n_cal=4)
    router = MultiModelEngine({
        "mobilenet_v2": VisionEngine(
            qnet, buckets=(2, 4), mesh=mesh, tracer=tracer,
            metrics=metrics, name="mobilenet_v2"),
        "efficientnet_compact": VisionEngine(
            effq, buckets=(2, 4), mesh=mesh, tracer=tracer,
            metrics=metrics, name="efficientnet_compact"),
    })
    router.warmup()
    now = time.perf_counter()
    handles = [router.submit("mobilenet_v2" if i % 2 == 0
                             else "efficientnet_compact", img,
                             deadline_s=now + (1.0 if i % 4 == 1 else 10.0))
               for i, img in enumerate(images[:8])]
    res = router.run()
    router.stats()  # refresh the fps / fps-per-watt gauges
    print(f"multi-model: {sum(res[h].status == 'ok' for h in handles)}/8 ok, "
          f"dispatch order {[m for m, _ in router.dispatch_log]}")

    # 6. export the trace (drop into https://ui.perfetto.dev) + summarize
    trace_path = os.path.join(tempfile.gettempdir(), "serve_vision_trace.json")
    tracer.save(trace_path)
    print(f"trace ({len(tracer)} events) -> {trace_path}")
    print(render_report(summarize_trace(tracer.to_chrome()),
                        metrics.snapshot()))


if __name__ == "__main__":
    main()
