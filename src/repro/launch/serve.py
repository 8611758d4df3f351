"""Serving launcher: batched decode with continuous slot refill.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --reduced --requests 8 --max-new 24
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro import compile_cache
from repro.configs.registry import ARCHS, get_arch
from repro.models import transformer
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.runtime.server import DecodeServer, Request


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--admission", default="fifo",
                    choices=("fifo", "model"),
                    help="slot-refill policy: arrival order or "
                         "shortest-predicted-job-first")
    ap.add_argument("--trace-json", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the serve "
                         "run (prefill/decode spans + predicted overlay)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the metrics registry as JSON on exit")
    # --- supervised degradation / chaos (runtime/supervisor.py) ---
    ap.add_argument("--supervise", action="store_true",
                    help="run under ServingSupervisor: decode watchdog, "
                         "slot eviction, admission throttling, load "
                         "shedding with retry-after")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC|PATH",
                    help="deterministic fault schedule (iteration-indexed"
                         "); implies --supervise")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--slo-decode-s", type=float, default=None,
                    help="decode-iteration latency SLO (admission defers "
                         "when the model predicts a breach)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="shed queued requests beyond this depth "
                         "(stamped with retry-after)")
    return ap.parse_args(argv)


def build_server(args: argparse.Namespace, injector=None) -> DecodeServer:
    """The server ``args`` describe, with weights drawn from ``--seed``."""
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    return DecodeServer(cfg, params, slots=args.slots,
                        max_len=args.max_len, seed=args.seed,
                        admission=args.admission,
                        slo_decode_s=args.slo_decode_s,
                        injector=injector)


def make_requests(args: argparse.Namespace, vocab_size: int
                  ) -> List[Request]:
    """``--requests`` prompts of 4–16 random tokens each, from ``--seed``."""
    rng = np.random.default_rng(args.seed)
    reqs = []
    for rid in range(args.requests):
        plen = int(rng.integers(4, 17))
        prompt = rng.integers(2, vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(rid=rid, prompt=prompt, max_new=args.max_new))
    return reqs


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    compile_cache.enable_jax_cache()
    if args.trace_json:
        _obs_trace.enable(process_name="serve")

    supervised = args.supervise or args.fault_plan
    injector = None
    if supervised:
        from repro.runtime.faults import FaultInjector, FaultPlan
        fplan = FaultPlan.parse(args.fault_plan, seed=args.chaos_seed) \
            if args.fault_plan else FaultPlan(seed=args.chaos_seed)
        injector = FaultInjector(fplan)
        if fplan:
            print(f"[serve] fault plan armed: {fplan.describe()}")
    server = build_server(args, injector)
    for req in make_requests(args, server.cfg.vocab_size):
        server.submit(req)

    t0 = time.perf_counter()
    if supervised:
        from repro.runtime.supervisor import ServingPolicy, ServingSupervisor
        sup = ServingSupervisor(
            server, ServingPolicy(max_queue=args.max_queue),
            injector=injector)
        done = sup.run()
        sup.report()
    else:
        done = server.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, slots={args.slots})")
    for r in done[:3]:
        print(f"  req {r.rid}: prompt[:6]={r.prompt[:6].tolist()} "
              f"out[:8]={r.out[:8]}")

    tracer = _obs_trace.get_tracer()
    if args.trace_json:
        for line in tracer.report_lines():
            print(f"[trace] {line}")
        tracer.save(args.trace_json)
        print(f"[serve] trace written to {args.trace_json}")
    if args.metrics_json:
        _obs_metrics.REGISTRY.save_json(args.metrics_json)
        print(f"[serve] metrics written to {args.metrics_json}")


if __name__ == "__main__":
    main()
