"""On-chip smoke run of the main path on one TPU.

    python chip_smoke.py

One process, five phases in order, each through the entry points a user
calls, at full model width with random weights drawn from fixed seeds:

  device     refuse to run anywhere but a TPU (never falls back to the CPU)
  kernels    the four Pallas kernels compiled for the chip
             (``interpret=False``, ``block_sizes="auto"``) at main-path
             widths, each checked against ``repro.kernels.ref``
  train      a few smollm-360m steps through ``repro.launch.train``'s
             config builder and the ``Trainer`` (XLA path)
  serve      a smollm-360m ``DecodeServer`` answering 8 requests, with one
             prompt's decode logits checked against ``transformer.forward``
  calibrate  ``repro.calibration.calibrate`` on a few kernel classes

Each phase prints one line: what it ran, compile seconds, steady seconds
and the device's ``peak_bytes_in_use`` so far.  These are bring-up numbers,
not benchmark results.  A failed phase prints its traceback and makes the
run exit 1; only when every phase passed does the last line read
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: kernel widths: smollm-360m attention, mamba2-370m SSD, 4096² matmul and
#: transpose, batch as in the train phase
FLASH = dict(B=4, H=15, KVH=5, S=2048, dh=64)
SSD = dict(Bz=4, H=32, G=1, L=2048, P=64, N=128)
SQUARE = 4096
TRAIN_ARGV = ["--arch", "smollm-360m", "--steps", "5", "--batch", "4",
              "--seq", "2048"]
SERVE_ARGV = ["--arch", "smollm-360m", "--requests", "8", "--slots", "4",
              "--max-new", "16", "--max-len", "2048"]
CALIB_CLASSES = ("mm_tiled", "stride1_global", "vector_scale_add")


def _peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _report(dev, phase: str, compile_s, steady_s, **extra) -> None:
    fields = {"compile_s": compile_s, "steady_s": steady_s, **extra,
              "peak_bytes_in_use": _peak_bytes(dev)}
    print(f"[smoke] {phase}: " + " ".join(f"{k}={v}" for k, v in
                                         fields.items()), flush=True)


def _steady(fn, args, reps: int = 5) -> float:
    """Median seconds of ``reps`` blocked calls (compiled already)."""
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _close(name: str, got, want, *, atol: float, rtol: float) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                               err_msg=name)
    return float(np.max(np.abs(got - want)))


def phase_kernels(dev) -> None:
    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    f = FLASH
    q = jax.random.normal(keys[0], (f["B"], f["H"], f["S"], f["dh"]),
                          jnp.bfloat16)
    k, v = (jax.random.normal(kk, (f["B"], f["KVH"], f["S"], f["dh"]),
                              jnp.bfloat16) for kk in keys[1:3])
    s = SSD
    x = (jax.random.normal(keys[3], (s["Bz"], s["H"], s["L"], s["P"]))
         * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[4], (s["Bz"], s["H"],
                                                     s["L"])))
    A = -jnp.exp(jax.random.normal(keys[5], (s["H"],)) * 0.3)
    Bm, Cm = (jax.random.normal(kk, (s["Bz"], s["G"], s["L"], s["N"])) * 0.3
              for kk in keys[6:8])
    a = jax.random.normal(keys[0], (SQUARE, SQUARE), jnp.bfloat16)
    b = jax.random.normal(keys[1], (SQUARE, SQUARE), jnp.bfloat16)

    auto = dict(block_sizes="auto", interpret=False)
    cases = [
        ("flash_attention",
         lambda q, k, v: ops.flash_attention(q, k, v, causal=True, **auto),
         (q, k, v)),
        ("ssd_scan",
         lambda *xs: ops.ssd_scan(*xs, **auto), (x, dt, A, Bm, Cm)),
        ("matmul", lambda a, b: ops.matmul(a, b, **auto), (a, b)),
        ("transpose", lambda a: ops.transpose(a, **auto), (a,)),
    ]
    for name, fn, args in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Pallas kernel in the program")
        steady_s = _steady(compiled, args)
        out = compiled(*args)
        # the oracles run at full f32 matmul precision on the chip too;
        # tolerances are tests/test_kernels.py's for these dtypes
        with jax.default_matmul_precision("highest"):
            if name == "flash_attention":
                err = _close(name, out, ref.attention(q, k, v, causal=True),
                             atol=2e-2, rtol=2e-2)
            elif name == "ssd_scan":
                y_ref, h_ref = ref.ssd(*args)
                err = max(_close("ssd_scan y", out[0], y_ref,
                                 atol=3e-2, rtol=3e-2),
                          _close("ssd_scan h", out[1], h_ref,
                                 atol=5e-4, rtol=5e-4))
            elif name == "matmul":
                err = _close(name, out, ref.matmul(a, b), atol=1.0,
                             rtol=3e-2)
            else:
                err = _close(name, out, ref.transpose(a), atol=0, rtol=0)
        _report(dev, f"kernels/{name}", f"{compile_s:.3f}",
                f"{steady_s:.6f}",
                shapes="/".join("x".join(map(str, t.shape)) for t in args),
                max_abs_err=err)


def phase_train(dev) -> None:
    from repro.launch import train as train_launch
    from repro.runtime.trainer import Trainer

    args = train_launch.parse_args(TRAIN_ARGV)
    cfg, dc, tc = train_launch.trainer_config(args)
    trainer = Trainer(cfg, dc, tc)
    batch = {k: jnp.asarray(v) for k, v in trainer.loader.batch(0).items()}
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(trainer.state, batch).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    limit = int(dev.memory_stats()["bytes_limit"])
    print(f"[smoke] train: step program needs {need} of {limit} bytes "
          f"(arguments {ma.argument_size_in_bytes}, outputs "
          f"{ma.output_size_in_bytes}, temporaries {ma.temp_size_in_bytes})",
          flush=True)
    if need > limit:
        raise AssertionError(f"train step needs {need} B > {limit} B")

    hist = trainer.train(args.steps)
    losses = [h["loss"] for h in hist]
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    # random init: the first loss is the entropy of a near-uniform softmax
    if abs(losses[0] - math.log(cfg.vocab_size)) > 1.0:
        raise AssertionError(f"first loss {losses[0]} is far from "
                             f"ln(V) = {math.log(cfg.vocab_size):.3f}")
    steady_s = float(np.median([h["time_s"] for h in hist[1:]]))
    tokens = args.batch * args.seq
    _report(dev, "train", f"{compile_s:.3f}", f"{steady_s:.6f}",
            path="xla", arch=cfg.name, batch=args.batch, seq=args.seq,
            steps=len(hist), first_step_s=f"{hist[0]['time_s']:.3f}",
            tokens_per_s=f"{tokens / steady_s:.1f}",
            loss=f"{losses[0]:.4f}->{losses[-1]:.4f}")


def phase_serve(dev) -> None:
    from repro.launch import serve as serve_launch
    from repro.models import transformer

    args = serve_launch.parse_args(SERVE_ARGV)
    server = serve_launch.build_server(args)
    cfg, params = server.cfg, server.params
    reqs = serve_launch.make_requests(args, cfg.vocab_size)

    tok = jnp.zeros((args.slots, 1), jnp.int32)
    t0 = time.perf_counter()
    server._decode.lower(params, server.state, tok).compile()
    compile_s = time.perf_counter() - t0

    # the first prompt goes through the server's own prefill on a fresh
    # cache; its last logits must agree with a full-sequence forward
    first = reqs[0]
    got = server._prefill_slot(0, first)
    forward = jax.jit(
        lambda p, t: transformer.forward(p, cfg, {"tokens": t})[0])
    want = forward(params, jnp.asarray(first.prompt[None]))[0, -1]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    max_abs = float(np.max(np.abs(got - want)))
    if not (rel_l2 < 5e-2 and max_abs < 0.25):
        raise AssertionError(f"decode logits differ from forward: rel_l2="
                             f"{rel_l2} max_abs={max_abs}")

    for r in reqs[1:]:
        server.submit(r)
    prompt_tokens = sum(len(r.prompt) for r in reqs[1:])
    t0 = time.perf_counter()
    done = server.run()
    run_s = time.perf_counter() - t0
    if sorted(r.rid for r in done) != [r.rid for r in reqs]:
        raise AssertionError(f"completed {sorted(r.rid for r in done)}")
    for r in done:
        ended = len(r.out) == r.max_new or r.out[-1] == server.eos_id
        if not ended or not all(0 <= t < cfg.vocab_size for t in r.out):
            raise AssertionError(f"request {r.rid}: out={r.out}")
    decode_calls = server._iters + prompt_tokens
    _report(dev, "serve", f"{compile_s:.3f}",
            f"{run_s / decode_calls:.6f}", path="xla", arch=cfg.name,
            requests=len(done), slots=args.slots, max_len=args.max_len,
            tokens=sum(len(r.out) for r in done), decode_calls=decode_calls,
            logits_rel_l2=rel_l2, logits_max_abs=max_abs)


def phase_calibrate(dev) -> None:
    from repro.calibration import calibrate

    res = calibrate("chip-smoke", scale="tiny", runs=8,
                    drop=2, classes=CALIB_CLASSES, write_registry=False,
                    verbose=False)
    meta = res.model.meta
    if (meta["platform"], meta["device_kind"]) != ("tpu", dev.device_kind):
        raise AssertionError(f"model meta names {meta['platform']} "
                             f"{meta['device_kind']}")
    geo = res.report["geomean_rel_err"]
    if not (np.all(np.isfinite(res.model.weights)) and math.isfinite(geo)):
        raise AssertionError(f"non-finite fit: {res.model.weights} {geo}")
    steady_s = sum(r["actual_s"] for r in res.report["rows"])
    _report(dev, "calibrate", "not-separated", f"{steady_s:.6f}",
            kernels=len(res.labels), classes=",".join(CALIB_CLASSES),
            wall_s=f"{res.wall_s:.3f}",
            launch_overhead_s=f"{res.launch_overhead_s:.3e}",
            fit_geomean_rel_err=f"{geo:.4f}",
            meta=f"{meta['platform']}/{meta['device_kind']}"
                 f"/{meta['device_count']}")


PHASES = (("kernels", phase_kernels), ("train", phase_train),
          ("serve", phase_serve), ("calibrate", phase_calibrate))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro import compile_cache
    cache = compile_cache.enable_jax_cache()
    print(f"[smoke] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)

    failed = []
    for name, phase in PHASES:
        try:
            phase(dev)
        except Exception:
            traceback.print_exc()
            print(f"[smoke] {name}: FAILED", flush=True)
            failed.append(name)
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
