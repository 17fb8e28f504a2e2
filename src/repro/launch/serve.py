"""Serving driver: batched requests through the Engine.

LM serving:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
        --requests 8 [--quant-bits 8]

Vision serving at the published design points (MobileNetV2 alpha 1.0 at
224, compact EfficientNet at 128), multi-model, sharded over 4 replicas:

    PYTHONPATH=src python -m repro.launch.serve --vision --replicas 4 \
        --models mobilenet_v2,efficientnet_compact --requests 32

(on a CPU host, `XLA_FLAGS=--xla_force_host_platform_device_count=4`
provides the 4 devices; `--alpha 0.35 --hw 48` is a quick CPU-sized point).

Serve-time weight quantization (--quant-bits) applies the paper's range-based
symmetric per-channel scheme to every linear operator — the LM analogue of
QNet deployment. --vision instead serves calibrated integer QNets through
the pipelined CU stage executors: --replicas builds a 1-D 'data' mesh and
shards every micro-batch across it; more than one --models entry routes
requests through the EDF `MultiModelEngine`. --tuned-cache serves through
a committed per-op route selection (see `repro.tune`); --tune measures one
live first. --trace-out exports the request-lifecycle Chrome trace
(Perfetto-loadable), --metrics-out the metrics registry (Prometheus text
for .prom/.txt, JSON snapshot otherwise); `python -m repro.obs summarize`
renders either into a pipeline-profile report.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import ARCHS, get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.lm import model as M
from repro.serve.engine import Engine, Request

VISION_ARCHS = ("mobilenet_v2", "efficientnet_compact")


def _vision_qnet(arch: str, hw: Optional[int] = None, alpha: float = 1.0,
                 seed: int = 0):
    """Calibrated QNet of a published design point: `configs/<arch>.py`
    at its published input resolution unless `hw` overrides it; `alpha`
    is MobileNetV2's width multiplier."""
    from repro.configs import efficientnet_compact, mobilenet_v2
    from repro.models import layers

    kw = {} if hw is None else {"input_hw": hw}
    if arch == "mobilenet_v2":
        net = mobilenet_v2.get_config(alpha=alpha, **kw)
    elif arch == "efficientnet_compact":
        net = efficientnet_compact.get_config(**kw)
    else:
        raise ValueError(f"unknown vision arch {arch!r} (pick from {VISION_ARCHS})")
    return layers.make_calibrated_qnet(net, seed=seed)


def _vision_tuned(args, qnets):
    """Resolve the serving route selection: tune live (--tune), or load a
    committed cache (--tuned-cache). Returns a TunedPlan or None."""
    if args.tune:
        import functools

        from repro.tune import save_tuned, tune_qnet

        plans = [tune_qnet(q, batch=args.batch) for q in qnets.values()]
        tuned = functools.reduce(lambda a, b: a.merge(b), plans)
        if args.tuned_cache:
            save_tuned(tuned, args.tuned_cache)
            print(f"[serve-vision] tuned {len(tuned)} entries "
                  f"-> {args.tuned_cache}")
        return tuned
    if args.tuned_cache:
        from repro.tune import load_tuned

        tuned = load_tuned(args.tuned_cache)
        print(f"[serve-vision] loaded tuning cache {args.tuned_cache} "
              f"({len(tuned)} entries)")
        return tuned
    return None


def vision_main(args):
    """Serve `args.requests` random images through the EDF router.
    Returns (router, sent, results): `sent` maps each (model, rid) handle
    to the image submitted under it, `results` is `router.run()`'s."""
    from repro.dist.sharding import data_mesh
    from repro.serve.vision import MultiModelEngine, VisionEngine

    tracer = metrics = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()  # one tracer across models = one timeline
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    mesh = data_mesh(args.replicas) if args.replicas > 1 else None
    # --batch bounds the largest micro-batch; the engine rounds buckets up
    # to replica multiples itself
    buckets = tuple(sorted(
        {b for b in (1, 2, 4) if b < args.batch} | {args.batch}))
    models = [m.strip() for m in args.models.split(",") if m.strip()]
    t0 = time.perf_counter()
    qnets = {m: _vision_qnet(m, args.hw, args.alpha, args.seed)
             for m in models}
    print(f"[serve-vision] calibrated {', '.join(qnets[m].spec.name for m in models)} "
          f"in {time.perf_counter() - t0:.2f}s")
    tuned = _vision_tuned(args, qnets)
    if tuned is not None:
        for m, q in qnets.items():
            print(f"[serve-vision] {m}: tuned route coverage "
                  f"{tuned.coverage(q):.0%}")
    engines = {
        m: VisionEngine(qnets[m], mesh=mesh, buckets=buckets, tuned=tuned,
                        tracer=tracer, metrics=metrics, name=m)
        for m in models
    }
    router = MultiModelEngine(engines, power_budget_w=args.power_budget_w)
    if args.power_budget_w:
        print(f"[serve-vision] power cap {args.power_budget_w:.1f} W "
              f"shared across {len(models)} model(s)")
    t0 = time.perf_counter()
    router.warmup()
    print(f"[serve-vision] warmup (every stage x bucket traced and "
          f"compiled) {time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng(args.seed)
    now = time.perf_counter()
    sent = {}
    for i in range(args.requests):
        m = models[i % len(models)]
        img = rng.uniform(-1, 1, qnets[m].spec.input_shape()).astype(
            np.float32)
        deadline = now + 5.0 if i % 3 == 0 else None
        sent[router.submit(m, img, deadline_s=deadline)] = img
    results = router.run()
    n_ok = sum(1 for r in results.values() if r.status == "ok")
    print(f"[serve-vision] {n_ok}/{len(results)} ok over "
          f"{len(models)} model(s), {args.replicas} replica(s)")
    for m, st in sorted(router.stats().items()):
        print(f"[serve-vision] {m}: fps={st.fps:.1f} "
              f"p95={st.latency_p95_s*1e3:.1f}ms "
              f"micro_batches={st.micro_batches} replicas={st.replicas}")
        print(f"[serve-vision] {m}: "
              f"{st.energy_j_per_image*1e6:.1f} uJ/image "
              f"({st.power_source}) -> {st.watts:.1f} W, "
              f"{st.fps_per_watt:.1f} fps/W"
              + (f", shed={st.n_shed} deferred={st.n_deferred}"
                 if args.power_budget_w else ""))
    if tracer is not None:
        print(f"[serve-vision] trace -> {tracer.save(args.trace_out)} "
              f"({len(tracer)} events; load in https://ui.perfetto.dev)")
    if metrics is not None:
        print(f"[serve-vision] metrics -> {metrics.save(args.metrics_out)}")
    if tracer is not None or metrics is not None:
        from repro.obs import render_report, summarize_trace
        print(render_report(
            summarize_trace(tracer.to_chrome()) if tracer else None,
            metrics.snapshot() if metrics else None))
    return router, sent, results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vision", action="store_true",
                    help="serve integer vision QNets instead of an LM")
    ap.add_argument("--models", default="mobilenet_v2",
                    help="comma-separated vision model list "
                         f"(from {', '.join(VISION_ARCHS)})")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas (vision; needs devices)")
    ap.add_argument("--hw", type=int, default=None,
                    help="vision input H=W (default: each arch's published "
                         "resolution, 224 / 128)")
    ap.add_argument("--alpha", type=float, default=1.0,
                    help="MobileNetV2 width multiplier (published: 1.0, "
                         "0.75, 0.5, 0.35)")
    ap.add_argument("--batch", type=int, default=8,
                    help="largest vision micro-batch bucket")
    ap.add_argument("--tune", action="store_true",
                    help="autotune per-op routes for each vision model "
                         "before serving (saved to --tuned-cache if given)")
    ap.add_argument("--power-budget-w", type=float, default=None,
                    help="shared modeled-power cap in watts for vision "
                         "serving: one rolling-window governor across all "
                         "models defers/sheds work to stay under the cap "
                         "(docs/energy.md)")
    ap.add_argument("--tuned-cache", default=None,
                    help="tuning-cache JSON to load (or write, with "
                         "--tune) for vision serving")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace of the vision serving run "
                         "(Perfetto-loadable request-lifecycle timeline)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the vision metrics registry (.prom/.txt = "
                         "Prometheus text, else JSON snapshot)")
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--quant-bits", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.vision:
        return vision_main(args)

    import dataclasses
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.quant_bits:
        cfg = dataclasses.replace(cfg, quant_bits=args.quant_bits)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = Engine(cfg, params, batch_slots=args.slots, max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
            max_new=args.max_new,
            temperature=0.0 if i % 2 == 0 else 0.8,
        ))
    done = eng.run()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in done.values())
    for rid in sorted(done):
        print(f"[serve] req {rid}: {done[rid][:8]}... ({len(done[rid])} tokens)")
    print(f"[serve] {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)", flush=True)
    return done


if __name__ == "__main__":
    main()
