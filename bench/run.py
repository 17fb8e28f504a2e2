#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` `workloads`) names a configuration
(`bench/configs/<file>.json`, built by `bench/models/<family>.py`) and a
traffic mix (`bench/traffic/<name>.json`); its per-layer metrics are read by
`bench/layer_metrics/<metric>.py`. Nothing here lists cells, models or
metrics by name.

Set-up (timed as `setup_s`, process start to the first timed request):
weights and integer tables from the configuration's weight seed
(`deploy.py`, one device program), the program's serving engine over them,
every stage program of the cell's buckets warmed, one drain per bucket.
A cell on several chips serves over a 'data' mesh of exactly those chips:
each micro-batch is split into one shard of rows per chip.
`--seed` draws the images, the arrival phases and jitter, and the sample of
answers checked; the weights stay those of the configuration, so every run
serves the same compiled programs (the program compiles its weights into
its stage programs, and a new deployment per seed recompiled them in every
run and left a post-compile stall in the window). The window then offers the traffic
for `--seconds`. `--trace 0` reports the cell's end-to-end metrics;
`--trace 1` runs the same window with the program's span tracer on and the
JAX profiler over a slice of it, and reports the per-layer metrics.

After the window a sample of the answers, drawn from the seed, is compared
with the plain integer reference (`reference.py`): logits must be equal bit
for bit, and every request due in the window must have been answered.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, [`breakdown`], `checks`).
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

import counts  # noqa: E402
import deploy  # noqa: E402
import net as netlib  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as trafficlib  # noqa: E402
from stats import percentile  # noqa: E402

TRACE_SECONDS = 2.0  # the profiled slice: the last seconds of a --trace 1 window
SAMPLE = 64  # answers compared with the reference in every run


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: str  # configuration name
    cfg: Dict
    traffic: Dict
    end_to_end: List[Dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]


def resolve(bench: Dict, workload: str) -> Cell:
    """The cell named `workload`, with its files loaded; unknown names fail."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in cfgs:
        raise SystemExit(f"bench: unknown configuration {w['config']!r}")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(workload, w["chips"], w["config"],
                netlib.load_config(ROOT / cfgs[w["config"]]["file"]),
                trafficlib.load(w["traffic"]), e2e, layer)


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX finds {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devices[:chips]


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), small programs included."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def reader(metric: str):
    path = BENCH / "layer_metrics" / f"{metric}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no reader {path} for per-layer metric {metric}")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class LayerInputs:
    """What a per-layer reader may read (`layer_metrics/<name>.py`)."""

    blocks: List[netlib.Block]
    input_hw: int
    peak: Dict  # peaks.json entry of this device kind
    macs_per_image: int
    trace: Optional[trace_reduce.Reduced]  # profiled slice, None if empty
    traced_batches: List[int]  # rows (padding included) per micro-batch in it
    traced_images: int  # images answered by drains inside it
    spans: List[Dict]  # the program's span tracer, Chrome events of the window
    chips: int  # devices the cell ran on (its data mesh's replicas)


class _Profiler:
    """Profiles the whole drains of the window's last TRACE_SECONDS.

    Device activity only (`host_tracer_level` 0): host tracing records
    every step of the program's input transfers and slowed its drains
    several-fold on the chip. What the host was doing comes from the
    benchmark's own annotations (`annotate`), timed on the wall clock the
    profiler stamps its session with (`profile_start_time`)."""

    def __init__(self, router, buckets, seconds: float, directory: str):
        self.router, self.buckets, self.dir = router, buckets, directory
        self.start_at = max(0.0, seconds - TRACE_SECONDS)
        self.first = self.last = None  # wall-clock ns of the profiled slice
        self.done = False
        self.batches: List[int] = []
        self.images = 0
        self.notes: List[tuple] = []  # (name, start, end), wall-clock ns

    @contextlib.contextmanager
    def annotate(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            if self.first is not None and not self.done:
                self.notes.append((name, t0, time.time_ns()))

    def between(self, elapsed: float) -> None:
        import jax

        if self.done:
            return
        if self.first is None:
            if elapsed >= self.start_at:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.first = time.time_ns()
            return
        self.last = time.time_ns()
        for _, live in self.router.dispatch_log:
            self.batches.append(min(b for b in self.buckets if b >= live))
            self.images += live

    def stop(self) -> None:
        import jax

        if self.first is not None and not self.done:
            jax.profiler.stop_trace()
        self.done = True

    def reduced(self) -> Optional[trace_reduce.Reduced]:
        files = sorted(Path(self.dir).rglob("*.xplane.pb"))
        if not files or self.last is None:
            return None
        profile = trace_reduce.load(str(files[-1]))
        t0 = trace_reduce.profile_start_ns(profile)
        print(f"bench: profiled {self.images} images in {len(self.batches)} "
              f"micro-batches over {(self.last - self.first) * 1e-9:.3f} s; trace "
              f"{files[-1].stat().st_size} bytes", file=sys.stderr)
        if t0 is None:
            return None
        window = (self.first - t0, self.last - t0)
        notes = [(n, a - t0, b - t0) for n, a, b in self.notes]
        with open(Path(self.dir) / "notes.json", "w") as f:  # kept with --keep-trace
            json.dump({"window": window, "notes": notes}, f)
        return trace_reduce.reduce(profile, window, notes)


def check_answers(dep, pool, win) -> Dict[str, Dict]:
    """Compare the window's sampled answers with the plain reference."""
    images = sorted({win.image[k] for k, _ in win.sample})
    ref = dict(zip(images, reference.logits(dep, pool[images])))
    mismatched = sum(int(np.count_nonzero(np.asarray(logits, np.float32)
                                          != ref[win.image[k]]))
                     for k, logits in win.sample)
    return {"unanswered": {"value": len(win.ok) - sum(win.ok), "limit": 0},
            "mismatched_logits": {"value": mismatched, "limit": 0}}


def engine_mesh(devices, buckets):
    """None on one chip; else a 'data' mesh over exactly the cell's devices.
    Every bucket has to split evenly: the engine would round it up, and the
    profiler counts rows by the traffic's buckets."""
    if len(devices) == 1:
        return None
    from repro.dist.sharding import data_mesh

    if any(b % len(devices) for b in buckets):
        raise SystemExit(f"bench: buckets {list(buckets)} are not all multiples "
                         f"of the cell's {len(devices)} chips")
    return data_mesh(devices=devices)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, keep_trace: Optional[str] = None) -> Dict:
    from repro.obs import Tracer
    from repro.serve.vision import MultiModelEngine, VisionEngine

    buckets = sorted(cell.traffic["buckets"])
    mesh = engine_mesh(devices, buckets)
    fam = netlib.family(cell.cfg)
    blocks = fam.blocks(cell.cfg)
    net = fam.program_netspec(cell.cfg)
    netlib.check_same(blocks, net)
    macs = counts.macs_per_image(blocks, cell.cfg["input_hw"])
    if macs != cell.cfg["macs_per_image"]:
        raise SystemExit(f"bench: {cell.model} counts {macs} MACs per image, "
                         f"its file states {cell.cfg['macs_per_image']}")
    phases = {"start": time.perf_counter()}
    dep = deploy.build(cell.cfg, blocks, cell.cfg["weights"]["seed"])
    phases["deployment"] = time.perf_counter()
    rng = np.random.default_rng([seed, 2])
    pool = rng.uniform(-1, 1, (cell.traffic["pool"], *netlib.input_shape(cell.cfg))
                       ).astype(np.float32)
    tracer = Tracer() if trace else None
    # no deadline and no queue bound: a late frame is slow, never refused
    eng = VisionEngine(deploy.to_program_qnet(dep, net), buckets=buckets, mesh=mesh,
                       tracer=tracer, name=cell.model, max_queue=sys.maxsize)
    router = MultiModelEngine({cell.model: eng})
    phases["engine"] = time.perf_counter()
    router.warmup()
    phases["warmup"] = time.perf_counter()
    for b in buckets:  # one real drain per bucket, outside the window
        for i in range(b):
            router.submit(cell.model, pool[i % len(pool)])
        router.run()
    phases["first drains"] = time.perf_counter()
    print("bench: set-up " + ", ".join(
        f"{k} {b - a:.3f} s" for (_, a), (k, b) in zip(
            [("", t_start)] + list(phases.items())[:-1], phases.items())),
        file=sys.stderr)
    if tracer is not None:
        tracer.events.clear()

    import jax.monitoring

    compiles, window_open = [], [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if window_open[0] and "backend_compile" in event else None)
    pauses: Dict[int, List[float]] = {0: [], 1: [], 2: []}  # GC pauses in the window

    def on_gc(phase, info, t0=[0.0]):
        if phase == "start":
            t0[0] = time.perf_counter()
        elif window_open[0]:
            pauses[info["generation"]].append(time.perf_counter() - t0[0])
    gc.callbacks.append(on_gc)
    with tempfile.TemporaryDirectory() as tdir:
        prof = _Profiler(router, buckets, seconds, keep_trace or tdir)
        win = trafficlib.drive(
            router, cell.model, pool, cell.traffic, seconds, seed, SAMPLE,
            prof.between if trace else (lambda e: None),
            prof.annotate if trace else (lambda name: contextlib.nullcontext()))
        prof.stop()
        window_open[0] = False
        gc.callbacks.remove(on_gc)
        peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
        red = prof.reduced() if trace else None
    if red is not None and red.device_count != len(devices):
        raise SystemExit(f"bench: the trace holds {red.device_count} device planes, "
                         f"the cell ran on {len(devices)} chips")
    del router, eng

    setup_s = win.start - t_start
    t_ref = time.perf_counter()
    checks = check_answers(dep, pool, win)
    print(f"bench: compared {len(win.sample)} answers with the reference in "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    ok_lat = [r - s for r, s, ok in zip(win.returned, win.scheduled, win.ok) if ok]
    n_ok = len(ok_lat)
    values = {"setup_s": setup_s,
              "images_per_s": n_ok / (win.end - win.start)}
    if ok_lat:
        values["latency_p50_ms"] = 1e3 * percentile(ok_lat, 0.50)
        print(f"bench: latency p99 {1e3 * percentile(ok_lat, 0.99):.3f} ms (no bound: "
              f"host stalls make it bimodal)", file=sys.stderr)
    late = [s - d for s, d in zip(win.submitted, win.scheduled)]
    half = len(win.backlog) // 2
    print(f"bench: window {win.end - win.start:.3f} s, {len(win.ok)} requests, "
          f"{n_ok} ok, {len(win.backlog)} drains; generator lateness p50 "
          f"{1e3 * percentile(late, 0.5):.3f} ms p99 {1e3 * percentile(late, 0.99):.3f}"
          f" ms max {1e3 * max(late):.3f} ms; backlog at drain start max "
          f"{max(win.backlog)}, mean first half "
          f"{np.mean(win.backlog[:half] or [0]):.2f}, second half "
          f"{np.mean(win.backlog[half:]):.2f}; compiles in window {len(compiles)}; "
          f"set-up {setup_s:.3f} s; garbage collections in window " + ", ".join(
              f"gen{g} {len(p)} (longest {1e3 * max(p, default=0):.1f} ms)"
              for g, p in pauses.items()), file=sys.stderr)

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_mem)}
    out: Dict = {}
    if trace:
        inputs = LayerInputs(
            blocks=blocks, input_hw=cell.cfg["input_hw"],
            peak=counts.load_peak(d.device_kind), macs_per_image=macs, trace=red,
            traced_batches=prof.batches, traced_images=prof.images,
            spans=tracer.to_chrome()["traceEvents"], chips=len(devices))
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(inputs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            print("bench: device seconds by op and result type: " + "; ".join(
                f"{k} {v:.6f}" for k, v in red.top_ops(20, shapes=True)), file=sys.stderr)
            device["busy_s"], device["window_s"] = red.busy_s, red.window_s
            out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops()],
                                "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise SystemExit(f"bench: cell {cell.name} has no value for "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return {"correct": correct, "attempted": len(win.ok),
            "failed": len(win.ok) - n_ok, "metrics": metrics,
            "device": device, **out, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in (debugging)")
    args = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        cell = resolve(json.load(f), args.workload)
    devices = require_chips(cell.chips)
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      T_START, args.keep_trace)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
