#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-replica data mesh only

One chip: `repro.launch.serve`'s vision path serves MobileNetV2 alpha 1.0
at 224 and the compact EfficientNet at 128 (seeded weights, calibrated)
through the EDF multi-model router, buckets up to 8. Every answer must
equal `cu.run_qnet` bit for bit, both on the chip and on the host CPU of
the same process; every CU scope of the served program must hold a
Pallas kernel (`tpu_custom_call`), so an XLA route cannot pass for the
kernel path. A
DSCNN-KWS streaming session at the registered widths then steps a few hops
and must equal the full-window reference.

`--chips 4`: only MobileNetV2 alpha 1.0/224 over a 4-replica 'data' mesh,
compared bit for bit with one-device `cu.run_qnet`.

Each phase prints one JSON line (timings in seconds, mismatch counts). The
last line is {"ok": true, "device": {...}}. Any failure raises, and so
exits non-zero; so does a run where JAX finds no TPU — nothing falls back
to the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

N_REQUESTS = 16
MAX_BUCKET = 8
KWS_HOP = 2  # 20 ms of 10 ms frames; the stem's stride 2 must divide it
KWS_WINDOWS = 6


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check raises (an `assert` would vanish under -O)."""
    if not ok:
        raise RuntimeError(msg)


def require_tpu(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips asked for, JAX finds "
                         f"{len(devices)}")
    return devices


def serve(models, replicas: int):
    """The launcher's vision path; returns (router, sent, results)."""
    from repro.launch import serve as launch_serve

    t0 = time.perf_counter()
    router, sent, results = launch_serve.main([
        "--vision", "--models", ",".join(models), "--alpha", "1.0",
        "--replicas", str(replicas), "--requests", str(N_REQUESTS),
        "--batch", str(MAX_BUCKET)])
    not_ok = sorted(f"{m}/{rid}:{r.status}"
                    for (m, rid), r in results.items() if r.status != "ok")
    require(len(results) == len(sent) and not not_ok,
            f"{len(results)}/{len(sent)} answered, not ok: {not_ok}")
    log("serve", seconds=time.perf_counter() - t0, replicas=replicas,
        requests=len(results),
        nets={m: e.qnet.spec.name for m, e in router.engines.items()})
    return router, sent, results


def check_pallas(router) -> None:
    """Every CU scope of the served program must hold a Pallas TPU kernel."""
    t0 = time.perf_counter()
    kernels = {}
    for m, eng in router.engines.items():
        x = jax.ShapeDtypeStruct((eng.buckets[-1], *eng.input_shape),
                                 jnp.float32)
        text = eng.net._fn.lower(x).compile().as_text()
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        for st in eng.stages:  # op metadata reads jit(stage_net)/<cu>/...
            kernels[f"{m}/{st.spec.cu}"] = sum(
                f"/{st.spec.cu}/" in ln for ln in calls)
    missing = sorted(k for k, n in kernels.items() if n == 0)
    require(not missing, f"CU scopes without a Pallas kernel: {missing}")
    log("pallas", seconds=time.perf_counter() - t0, kernel_calls=kernels)


def _mismatch(a: np.ndarray, b: np.ndarray) -> dict:
    diff = a != b
    return {"elements": int(diff.sum()), "rows": int(diff.any(-1).sum()),
            "max_abs": float(np.abs(a - b).max()) if diff.any() else 0.0}


def check_reference(router, sent, results, on_cpu: bool) -> None:
    """Engine logits vs `cu.run_qnet` on one chip (and the host CPU)."""
    from repro.core import cu

    for m, eng in router.engines.items():
        keys = [k for k in sent if k[0] == m]
        x = np.stack([sent[k] for k in keys])
        got = np.stack([results[k].logits for k in keys])
        t0 = time.perf_counter()
        ref = {"chip": np.asarray(cu.run_qnet(eng.qnet, jnp.asarray(x)))}
        t_chip = time.perf_counter() - t0
        if on_cpu:
            with jax.default_device(jax.devices("cpu")[0]):
                ref["cpu"] = np.asarray(cu.run_qnet(eng.qnet, jnp.asarray(x)))
        counts = {f"engine_vs_{k}": _mismatch(got, v) for k, v in ref.items()}
        if on_cpu:
            counts["chip_vs_cpu"] = _mismatch(ref["chip"], ref["cpu"])
        log("reference", model=m, images=len(keys), run_qnet_chip_s=t_chip,
            seconds=time.perf_counter() - t0, mismatches=counts)
        bad = {k: c for k, c in counts.items() if c["elements"]}
        require(not bad, f"{m}: logits differ bitwise: {bad}")


def check_stream() -> None:
    """One DSCNN-KWS session, a few hops, vs the full-window reference."""
    from repro.configs.registry import get_netspec
    from repro.models.layers import make_calibrated_qnet
    from repro.serve import stream as ST

    t0 = time.perf_counter()
    qnet = make_calibrated_qnet(get_netspec("dscnn_kws"), seed=0)
    eng = ST.StreamEngine(qnet, KWS_HOP, name="kws")
    window = eng.window
    frames = np.random.default_rng(0).uniform(
        -1, 1, (ST.frames_for_windows(KWS_WINDOWS, window, KWS_HOP),
                eng.input_ch)).astype(np.float32)
    sid = eng.open_session()
    got = np.stack([r.logits for r in eng.push(sid, frames)])
    ref = ST.reference_windows(qnet, frames, window, KWS_HOP)
    counts = _mismatch(got, ref) if got.shape == ref.shape else None
    log("stream", seconds=time.perf_counter() - t0, net=qnet.spec.name,
        window=window, hop=KWS_HOP, windows=len(got), mismatches=counts)
    require(counts is not None and not counts["elements"],
            f"streaming windows differ from the full-window reference: "
            f"{got.shape} vs {ref.shape}, {counts}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the full smoke on one chip; 4: only the "
                         "4-replica data-mesh serving path")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    use_compile_cache()
    if args.chips == 4:
        router, sent, results = serve(["mobilenet_v2"], replicas=4)
        check_reference(router, sent, results, on_cpu=False)
    else:
        router, sent, results = serve(
            ["mobilenet_v2", "efficientnet_compact"], replicas=1)
        check_pallas(router)
        check_reference(router, sent, results, on_cpu=True)
        check_stream()
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
