# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  bench_table2              Table 2   (alpha x H: params / #ops)
  bench_bw_sweep            Fig. 13   (bit-width: size / SQNR / int inference)
  bench_table3              Table 3/4 (FPS per design point, roofline-projected)
  bench_fusion              Sec 5.1.2 (fused Body CU traffic reduction)
  bench_table6_efficientnet Table 6/7 (compact EfficientNet + CU mapping)
  bench_quant_serving       beyond-paper: LM weight-quantized serving
  bench_vision_serving      beyond-paper: pipelined CU-stage vision serving
                            (+ the multi-replica sharded scaling curve)
  bench_streaming           beyond-paper: ring-buffer streaming vs
                            full-window recompute on a 1-D DSCNN
                            (+ the batched multi-session fleet sweep)
  bench_kernels             kernel-level microbenchmarks

`--smoke` runs the fast subset (kernels + a reduced vision-serving pass +
the replica-scaling sweep + the streaming pass, all in this one process —
a child process could not reach an accelerator the parent holds) and
asserts the JSON reports still parse — the CI gate. A full (or smoke) run aggregates the per-benchmark results into a
perf-trajectory report at the repo root, BENCH_PR10.json: throughput /
latency / analytic bytes-moved, the calibrated energy model's J/image /
watts / FPS-per-Watt view of serving and streaming (docs/energy.md),
tuned-vs-default serving FPS (measured
per-op routes from the committed `experiments/tuned/` cache), the
obs-enabled serving FPS + metrics-snapshot profile (the observability
layer's <5% hot-path overhead budget, recorded as `obs_overhead_frac`),
the per-replica-count scaling curve (each point conformance-checked
against the frozen golden fixtures), the mixed-precision Pareto summary
(the committed `experiments/precision/` artifact the per-layer act-bit
search produced — front size, headline domination pair, per-point
objectives; see docs/tuning.md), plus deltas against the previous
PR's `experiments/vision_serving.json` baseline captured before this run
overwrote it. Force N CPU devices with
`XLA_FLAGS=--xla_force_host_platform_device_count=N` to exercise the
sharded points.

`--check-regression <baseline.json>` is the CI perf gate: after the run it
compares this report's throughput metrics against a committed baseline
report (e.g. BENCH_PR3.json) and FAILS on a >25% FPS regression
(`--regression-threshold` to tune), printing a full delta table. Only
same-config metrics can fail the gate — a smoke run compared against a
full-geometry baseline reports the deltas as informational — and latency /
kernel-microseconds rows are always informational (the gate is a
*throughput* gate; absolute wall times across heterogeneous CI machines
are too noisy to fail on).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.launch.compile_cache import use_compile_cache

BENCH_REPORT = "BENCH_PR10.json"
PRECISION_PARETO = "experiments/precision/mobilenet_v2_cpu_pareto.json"
VISION_REPORT = "experiments/vision_serving.json"
SCALING_REPORT = "experiments/vision_serving_scaling.json"
STREAMING_REPORT = "experiments/streaming.json"
STREAMING_BATCHED_REPORT = "experiments/streaming_batched.json"
TUNED_CACHE = "experiments/tuned/bench_cpu.json"


def _load_baseline(path: str):
    """The previous PR's vision-serving numbers (read before overwriting)."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (json.JSONDecodeError, OSError):
        return None


def _run_streaming(out: str, batched_out: str, n_sessions: int = 8) -> tuple:
    """bench_streaming in this process: the single-stream measurement,
    then the batched fleet sweep (`run_batched`). Both gated ratios
    (streaming vs full-window, drain() vs serial stepping) are measured
    in one process on one host, so they stay same-machine ratios. In
    process, not in a child: a process that has touched JAX holds the
    accelerator, and a child that needs it would fail or hang. Under a
    forced multi-device host (the scaling sweep's XLA_FLAGS) the steps
    share the split thread pool. Returns (streaming, streaming_batched)."""
    from benchmarks import bench_streaming

    streaming = bench_streaming.run(n_sessions=n_sessions, out=out)
    batched = bench_streaming.run_batched(out=batched_out)
    return streaming, batched


def _precision_summary(path: str = PRECISION_PARETO):
    """The committed mixed-precision Pareto artifact, trajectory-shaped:
    front size, the headline mixed-dominates-uniform pair, and each
    point's four objectives. None when no artifact is committed (the
    trajectory row is absent, not null-filled, pre-PR-10)."""
    if not os.path.exists(path):
        return None
    try:
        from repro.tune import precision as P
        doc = P.check_pareto_artifact(path)
        points = {p["name"]: {
            "accuracy": p["accuracy"],
            "fps": p["fps"],
            "us_per_image": p["us_per_image"],
            "model_bytes": p["model_bytes"],
            "j_per_image": p["j_per_image"],
            "uniform": p["uniform"],
        } for p in doc["points"]}
        dom = P.find_domination([P.PrecisionPoint(
            name=p["name"], block_bits=p["block_bits"], alloc=p["alloc"],
            uniform=p["uniform"], accuracy=p["accuracy"],
            us_per_image=p["us_per_image"], model_bytes=p["model_bytes"],
            j_per_image=p["j_per_image"], edp=p["edp"],
            tuned_fraction=p["tuned_fraction"]) for p in doc["points"]])
        return {
            "artifact": path,
            "model": doc["model"],
            "backend": doc["backend"],
            "choices": doc["choices"],
            "n_points": len(doc["points"]),
            "front": doc["pareto"],
            "domination": ({"mixed": dom[0], "uniform": dom[1]}
                           if dom else None),
            "points": points,
        }
    except (ValueError, KeyError, ImportError) as e:
        print(f"# precision artifact {path} unreadable: {e}",
              file=sys.stderr)
        return None


def _write_trajectory(vision, kernels, baseline, smoke: bool,
                      scaling=None, streaming=None,
                      streaming_batched=None) -> None:
    # deltas are only meaningful against a same-config baseline (smoke runs
    # a reduced geometry, so its trajectory carries absolute numbers only)
    if baseline and vision and (
            (baseline.get("input_hw"), baseline.get("batch"))
            != (vision["input_hw"], vision["batch"])):
        baseline = None
    pr1_fps = None
    if baseline:
        pr1_fps = baseline.get("fps_pipelined_fast",
                               baseline.get("fps_pipelined"))
    report = {
        "pr": 10,
        "smoke": smoke,
        "baseline_source": VISION_REPORT if baseline else None,
        "serving": None,
        "tuned": None,
        "observability": None,
        "scaling": None,
        "streaming": None,
        "streaming_batched": None,
        "precision": _precision_summary(),
        "kernels": kernels,
    }
    if vision:
        fast = vision["fps_pipelined_fast"]
        report["serving"] = {
            "net": vision["net"],
            "input_hw": vision["input_hw"],
            "batch": vision["batch"],
            "backend": vision["backend"],
            "fps_naive": vision["fps_naive"],
            "fps_monolith_jit": vision["fps_monolith_jit"],
            "fps_pipelined_pr1": vision["fps_pipelined"],
            "fps_pipelined_fast": fast,
            "fps_pipelined_tuned": vision.get("fps_pipelined_tuned"),
            "fps_pipelined_obs": vision.get("fps_pipelined_obs"),
            "latency_p50_s": vision["latency_p50_s"],
            "latency_p95_s": vision["latency_p95_s"],
            "bit_exact_with_run_qnet": vision["bit_exact_with_run_qnet"],
            "speedup_fast_vs_pr1_pipelined":
                vision["speedup_fast_vs_pipelined"],
            "pr1_baseline_fps": pr1_fps,
            "speedup_vs_pr1_baseline_file": (
                fast / pr1_fps if pr1_fps else None),
            "latency_p50_delta_vs_pr1_s": (
                vision["latency_p50_s"] - baseline["latency_p50_s"]
                if baseline and "latency_p50_s" in baseline else None),
            # calibrated energy model (docs/energy.md); absent from
            # pre-PR-9 baseline files, so every read tolerates None
            "energy_j_per_image": vision.get("energy_j_per_image"),
            "watts": vision.get("watts"),
            "fps_per_watt": vision.get("fps_per_watt"),
            "power_source": vision.get("power_source"),
            "energy_tuned_fraction": vision.get("energy_tuned_fraction"),
        }
        if vision.get("fps_pipelined_obs") is not None:
            # the serving profile as the obs layer saw it: headline FPS
            # with tracing+metrics on (the <5% overhead budget), plus the
            # registry snapshot's latency percentiles / FPS-per-Watt proxy
            snap = vision.get("obs_metrics_snapshot") or {}
            lat = (snap.get("histograms") or {}).get(
                'serve_request_latency_seconds{model="default"}') or {}
            report["observability"] = {
                "fps_obs_on": vision["fps_pipelined_obs"],
                "obs_overhead_frac": vision.get("obs_overhead_frac"),
                "bit_exact_with_obs_on":
                    vision.get("obs_bit_exact_with_run_qnet"),
                "trace_events": vision.get("obs_trace_events"),
                "latency_p50_s": lat.get("p50"),
                "latency_p95_s": lat.get("p95"),
                "latency_p99_s": lat.get("p99"),
                "fps_per_watt": (snap.get("gauges") or {}).get(
                    'serve_fps_per_watt{model="default"}'),
                "metrics_snapshot": snap,
            }
        if vision.get("tuned_cache"):
            report["tuned"] = {
                "cache": vision["tuned_cache"],
                "route_coverage": vision.get("tuned_route_coverage"),
                "fps_default": fast,
                "fps_tuned": vision.get("fps_pipelined_tuned"),
                "speedup_tuned_vs_default":
                    vision.get("speedup_tuned_vs_default"),
                "bit_exact_with_run_qnet":
                    vision.get("tuned_bit_exact_with_run_qnet"),
            }
    if scaling:
        report["scaling"] = {
            "device_count": scaling["device_count"],
            "input_hw": scaling["input_hw"],
            "batch": scaling["batch"],
            "replica_counts": scaling["replica_counts"],
            "fps_per_replica_count": {
                r: p["fps"] for r, p in scaling["curve"].items()},
            "speedup_max_replicas_vs_1":
                scaling["speedup_max_replicas_vs_1"],
            "all_bit_exact_incl_golden": scaling["all_bit_exact"],
            "golden_checked": scaling.get("golden_checked"),
        }
    if streaming:
        report["streaming"] = {
            "net": streaming["net"],
            "backend": streaming["backend"],
            "window": streaming["window"],
            "hop": streaming["hop"],
            "overlap_x": streaming["overlap_x"],
            "channels": streaming["channels"],
            "n_blocks": streaming["n_blocks"],
            "kernel": streaming["kernel"],
            "bit_exact_with_run_qnet":
                streaming["bit_exact_with_run_qnet"],
            "fps_full_window": streaming["fps_full_window"],
            "fps_streaming": streaming["fps_streaming"],
            "speedup_vs_full_window":
                streaming["speedup_vs_full_window"],
            "frames_computed_per_inference":
                streaming["frames_computed_per_inference"],
            "frames_full_window": streaming["frames_full_window"],
            "frames_ratio": streaming["frames_ratio"],
            "reuse_fraction": streaming["reuse_fraction"],
            "macs_ratio": streaming["macs_ratio"],
            "session_buffer_bytes": streaming["session_buffer_bytes"],
            "n_sessions": streaming["n_sessions"],
            "session_table_bytes": streaming["session_table_bytes"],
            "bytes_per_window_step": streaming.get("bytes_per_window_step"),
            "energy_j_per_window_step":
                streaming.get("energy_j_per_window_step"),
            "watts": streaming.get("watts"),
            "fps_per_watt": streaming.get("fps_per_watt"),
            "power_source": streaming.get("power_source"),
        }
    if streaming_batched:
        sb = streaming_batched
        report["streaming_batched"] = {
            "net": sb["net"],
            "backend": sb["backend"],
            "window": sb["window"],
            "hop": sb["hop"],
            "channels": sb["channels"],
            "n_blocks": sb["n_blocks"],
            "kernel": sb["kernel"],
            "sessions_sweep": sb["sessions_sweep"],
            "sessions_max": sb["sessions_max"],
            "batch_buckets": sb["batch_buckets"],
            "bit_exact_with_run_qnet": sb["bit_exact_with_run_qnet"],
            "per_sessions": sb["per_sessions"],
            "fps_serial_step": sb["fps_serial_step"],
            "fps_batched_step": sb["fps_batched_step"],
            "speedup_vs_serial_step": sb["speedup_vs_serial_step"],
            "pad_rows": sb["pad_rows"],
            "batched_traces": sb["batched_traces"],
        }
    if kernels:
        report["bytes_moved"] = {
            "dw_hbm_bytes": kernels.get("dw_hbm_bytes"),
            "dw_hbm_bytes_saved_vs_padded_copy":
                kernels.get("dw_hbm_bytes_saved_vs_padded"),
            "irb_fused_traffic_saved_frac":
                kernels.get("irb_bytes_saved_frac"),
            "pw_hbm_bytes": kernels.get("pw_hbm_bytes"),
        }
    with open(BENCH_REPORT, "w") as f:
        json.dump(report, f, indent=1)
    print(f"# wrote {BENCH_REPORT}", file=sys.stderr)


def _assert_reports_parse(*paths: str) -> None:
    for path in (BENCH_REPORT, *paths):
        with open(path) as f:
            json.load(f)  # raises on corruption — the CI smoke assertion


def _serving_config(report):
    s = (report or {}).get("serving") or {}
    return (s.get("input_hw"), s.get("batch"), s.get("backend"))


def _collect_throughput_rows(base, cur):
    """(name, base, cur, gated) rows for the regression table.

    `gated` == the row may FAIL the gate. Only the headline serving
    throughput (the pipelined fast/tuned FPS — the metrics this repo's
    perf work owns, measured over a full drain) gates, and only when the
    measurement config matches between baseline and current. Everything
    else is informational: naive/monolith/PR-1 FPS are tiny-sample eager
    baselines, the replica-scaling curve is flat at the machine ceiling
    on small hosts (spread ~1.2x — pure machine variance), and latency /
    kernel-microsecond rows are absolute wall times."""
    rows = []
    same_serving = (_serving_config(base) == _serving_config(cur)
                    and None not in _serving_config(cur))
    bs, cs = base.get("serving") or {}, cur.get("serving") or {}
    for key in ("fps_pipelined_fast", "fps_pipelined_tuned",
                "fps_per_watt"):
        # fps_per_watt is modeled-energy throughput (docs/energy.md);
        # pre-PR-9 baselines lack the key, so the row simply doesn't form
        if bs.get(key) is not None and cs.get(key) is not None:
            rows.append((f"serving.{key}", bs[key], cs[key], same_serving))
    for key in ("fps_pipelined_obs", "fps_pipelined_pr1",
                "fps_monolith_jit", "fps_naive",
                "latency_p50_s", "latency_p95_s"):
        if bs.get(key) is not None and cs.get(key) is not None:
            rows.append((f"serving.{key}", bs[key], cs[key], False))
    bst, cst = base.get("streaming") or {}, cur.get("streaming") or {}
    st_cfg = ("window", "hop", "channels", "n_blocks", "kernel", "backend")
    same_stream = (bst and cst
                   and all(bst.get(k) == cst.get(k) for k in st_cfg))
    # the speedup ratio is same-machine by construction (both routes run
    # on the same host in one process), so it gates even across
    # heterogeneous CI machines; frames_ratio is a pure function of the
    # plan — any drop means the halo math got worse, so it gates too
    for key in ("speedup_vs_full_window", "frames_ratio",
                "fps_per_watt"):
        if bst.get(key) is not None and cst.get(key) is not None:
            rows.append((f"streaming.{key}", bst[key], cst[key],
                         bool(same_stream)))
    for key in ("fps_streaming", "fps_full_window",
                "frames_computed_per_inference"):
        if bst.get(key) is not None and cst.get(key) is not None:
            rows.append((f"streaming.{key}", bst[key], cst[key], False))
    bsb = base.get("streaming_batched") or {}
    csb = cur.get("streaming_batched") or {}
    sb_cfg = ("window", "hop", "channels", "n_blocks", "kernel",
              "backend", "sessions_max", "batch_buckets")
    same_batched = (bsb and csb
                    and all(bsb.get(k) == csb.get(k) for k in sb_cfg))
    # serial-vs-drain() on the same host in one process: a same-machine
    # ratio, so it gates across heterogeneous CI machines like the
    # streaming speedup above
    if bsb.get("speedup_vs_serial_step") is not None \
            and csb.get("speedup_vs_serial_step") is not None:
        rows.append(("streaming_batched.speedup_vs_serial_step",
                     bsb["speedup_vs_serial_step"],
                     csb["speedup_vs_serial_step"], bool(same_batched)))
    for key in ("fps_batched_step", "fps_serial_step"):
        if bsb.get(key) is not None and csb.get(key) is not None:
            rows.append((f"streaming_batched.{key}",
                         bsb[key], csb[key], False))
    bsc, csc = base.get("scaling") or {}, cur.get("scaling") or {}
    bfps = bsc.get("fps_per_replica_count") or {}
    cfps = csc.get("fps_per_replica_count") or {}
    for r in sorted(set(bfps) & set(cfps), key=lambda v: int(v)):
        rows.append((f"scaling.fps_x{r}", bfps[r], cfps[r], False))
    bk, ck = base.get("kernels") or {}, cur.get("kernels") or {}
    for key in sorted(set(bk) & set(ck)):
        if key.endswith("_us") and isinstance(bk[key], (int, float)):
            rows.append((f"kernels.{key}", bk[key], ck[key], False))
    return rows


def check_regression(report, baseline, threshold: float = 0.25,
                     baseline_path: str = "") -> int:
    """Compare `report` against a committed baseline report; return the
    number of gated throughput metrics that regressed beyond `threshold`.

    `baseline` is the already-loaded baseline dict (callers snapshot it
    BEFORE the benchmark run — this run overwrites the report file the
    baseline may live in) or a path. Prints the full delta table either
    way — regressions, improvements, and informational
    (config-mismatched / latency) rows alike."""
    if isinstance(baseline, str):
        baseline_path = baseline_path or baseline
        try:
            with open(baseline) as f:
                baseline = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"[perf-gate] cannot read baseline {baseline}: {e}",
                  file=sys.stderr)
            return 1
    base = baseline
    rows = _collect_throughput_rows(base, report)
    if not rows:
        print(f"[perf-gate] no shared metrics with {baseline_path} — "
              f"nothing to gate", file=sys.stderr)
        return 0
    failures = 0
    name_w = max(len(r[0]) for r in rows)
    print(f"\n[perf-gate] vs {baseline_path} "
          f"(fail: gated fps metric down >{threshold:.0%})")
    print(f"{'metric':<{name_w}}  {'baseline':>12}  {'current':>12}  "
          f"{'delta':>8}  verdict")
    for name, b, c, gated in rows:
        higher_better = not (name.endswith("_s") or name.endswith("_us"))
        delta = (c - b) / b if b else float("inf")
        regressed = (delta < -threshold) if higher_better \
            else (delta > threshold)
        gateable = name in ("serving.fps_pipelined_fast",
                            "serving.fps_pipelined_tuned",
                            "serving.fps_per_watt",
                            "streaming.speedup_vs_full_window",
                            "streaming.frames_ratio",
                            "streaming.fps_per_watt",
                            "streaming_batched.speedup_vs_serial_step")
        if gated and regressed:
            verdict = "FAIL"
            failures += 1
        elif not gated:
            verdict = "info" + (" (config differs)" if gateable else "")
        else:
            verdict = "ok"
        print(f"{name:<{name_w}}  {b:>12.4g}  {c:>12.4g}  "
              f"{delta:>+7.1%}  {verdict}")
    if failures:
        print(f"[perf-gate] FAILED: {failures} throughput metric(s) "
              f"regressed >{threshold:.0%}", file=sys.stderr)
    else:
        print("[perf-gate] ok")
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset + JSON-report parse assertion (CI)")
    ap.add_argument("--tuned-cache", default=TUNED_CACHE,
                    help="tuning cache for the tuned-vs-default serving "
                         "measurement (skipped when absent)")
    ap.add_argument("--check-regression", metavar="BASELINE[:THRESHOLD]",
                    action="append", default=None,
                    help="after the run, gate this report's throughput "
                         "against a committed baseline report; repeatable; "
                         "an optional per-baseline :THRESHOLD overrides "
                         "--regression-threshold (e.g. BENCH_PR4.json:0.5 "
                         "for a cross-machine guard-rail)")
    ap.add_argument("--regression-threshold", type=float, default=0.25,
                    help="relative FPS drop that fails the gate")
    args = ap.parse_args(argv)
    use_compile_cache()

    # snapshot gate baselines BEFORE running: this run overwrites
    # BENCH_PR4.json, which is itself a valid (committed) baseline
    gate_baselines = []
    for spec in args.check_regression or ():
        path, sep, thr = spec.rpartition(":")
        try:
            threshold = float(thr) if sep else None
        except ValueError:
            threshold = None
        if threshold is None:
            path, threshold = spec, args.regression_threshold
        base = _load_baseline(path)
        gate_baselines.append((path, threshold, base))

    from benchmarks import (
        bench_bw_sweep,
        bench_fusion,
        bench_kernels,
        bench_quant_serving,
        bench_streaming,
        bench_table2,
        bench_table3,
        bench_table6_efficientnet,
        bench_vision_serving,
    )

    baseline = _load_baseline(VISION_REPORT)
    print("name,us_per_call,derived")
    failures = 0
    vision = kernels = scaling = streaming = streaming_batched = None

    # smoke must not clobber the committed perf-trajectory baseline with
    # reduced-size numbers
    vision_out = ("experiments/vision_serving_smoke.json" if args.smoke
                  else VISION_REPORT)
    scaling_out = ("experiments/vision_serving_scaling_smoke.json"
                   if args.smoke else SCALING_REPORT)
    streaming_out = ("experiments/streaming_smoke.json" if args.smoke
                     else STREAMING_REPORT)
    batched_out = ("experiments/streaming_batched_smoke.json" if args.smoke
                   else STREAMING_BATCHED_REPORT)
    if args.smoke:
        plan = [
            (bench_kernels, "kernels", lambda: bench_kernels.run()),
            (bench_vision_serving, "vision",
             lambda: bench_vision_serving.run(hw=32, n_images=16, repeats=1,
                                              out=vision_out,
                                              tuned_cache=args.tuned_cache)),
            (bench_vision_serving, "scaling",
             lambda: bench_vision_serving.run_scaling(
                 hw=32, n_images=16, repeats=1, out=scaling_out)),
            # same geometry AND windows/repeats as the committed baseline
            # (the speedup / frames_ratio gates compare like against
            # like; fewer timed windows makes the ~3ms streaming steps
            # noise-dominated and under-reports the speedup). Only the
            # session-table sizing is trimmed — it is untimed. The batched
            # fleet sweep keeps its
            # full default config too — its gated speedup_vs_serial_step
            # compares like against like with the committed baseline.
            (bench_streaming, "streaming",
             lambda: _run_streaming(streaming_out, batched_out, n_sessions=2)),
        ]
    else:
        plan = [
            (m, None, m.run) for m in (
                bench_table2, bench_bw_sweep, bench_table3, bench_fusion,
                bench_table6_efficientnet, bench_quant_serving)
        ] + [
            (bench_kernels, "kernels", lambda: bench_kernels.run()),
            (bench_vision_serving, "vision",
             lambda: bench_vision_serving.run(
                 tuned_cache=args.tuned_cache)),
            (bench_vision_serving, "scaling",
             lambda: bench_vision_serving.run_scaling(out=scaling_out)),
            (bench_streaming, "streaming",
             lambda: _run_streaming(streaming_out, batched_out)),
        ]

    for mod, slot, fn in plan:
        try:
            out = fn()
            if slot == "kernels":
                kernels = out
            elif slot == "vision":
                vision = out
            elif slot == "scaling":
                scaling = out
            elif slot == "streaming":
                streaming, streaming_batched = out
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)

    if args.tuned_cache and vision is not None \
            and not vision.get("tuned_cache"):
        # the tuned path was requested (CI passes the committed cache
        # explicitly) but the cache file was absent: failing loudly here
        # is what keeps the tuned fps gate row from silently vanishing
        # from the regression table. Opt out with --tuned-cache "".
        failures += 1
        print(f"benchmarks.run,0.0,ERROR:tuned cache {args.tuned_cache} "
              f"missing — tuned serving path was not exercised",
              file=sys.stderr)
    _write_trajectory(vision, kernels, baseline, args.smoke, scaling,
                      streaming, streaming_batched)
    if failures:
        # exit on the recorded benchmark errors before asserting report
        # files that a failed benchmark never wrote (a FileNotFoundError
        # here would bury the real cause)
        sys.exit(1)
    if args.smoke:
        _assert_reports_parse(vision_out, scaling_out, streaming_out,
                              batched_out)
    if gate_baselines:
        with open(BENCH_REPORT) as f:
            report = json.load(f)
        gate_failures = 0
        for path, threshold, base in gate_baselines:
            if base is None:
                print(f"[perf-gate] cannot read baseline {path}",
                      file=sys.stderr)
                gate_failures += 1
                continue
            gate_failures += check_regression(report, base, threshold,
                                              baseline_path=path)
        if gate_failures:
            sys.exit(2)


if __name__ == "__main__":
    main()
