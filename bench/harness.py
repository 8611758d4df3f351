"""One run of one cell: set-up, the checked first steps, the measured
window, the reference, and the result line.

Set-up builds the program's ``Trainer`` the way ``launch/train.py`` builds
it, gives it the benchmark's weights (one jitted call from the seed) and a
``PackedLoader`` over documents made from the seed, and drives it through
its first ``checked_steps`` steps with its own ``train`` call, which also
compiles (or loads from the cache) every program the window uses.  The
window then calls ``train`` a step at a time for ``--seconds``; with
``--trace 1`` it traces a few steps instead.  After the window the
program's state is freed and the float32 reference follows the checked
steps over the same weights and documents.

A ``--trace 1`` run also compiles the step afresh once the window has
closed (``program_trace.compile_step``: the HLO whose metadata names the
program's scopes, and the step's ``memory_analysis()``), and hands every
per-layer reader (``bench/metrics/<metric>.py``, a ``read(ctx)`` that
returns a number, or None where it finds nothing to read) one ``ctx``:

- ``reduced``: ``tracing.Reduced``, the device's busy time, idle gaps and
  top operations in the window of the benchmark's own spans (None where
  the trace holds no step or no device operation);
- ``program``: ``program_trace.ProgramTrace``, the program's own host
  spans and the device operations by the named scope they ran under (None
  where the fresh compile or the trace's load failed; the failure is
  logged);
- ``flops_per_step``: the reference's model FLOPs of one step;
- ``tokens_per_step``: rows × sequence length;
- ``peaks``: the chip's entry of ``peaks.json``;
- ``ref``: the configuration's reference module (``bench/models/``);
- ``config``, ``traffic``: the configuration's and the traffic mix's
  files, as loaded.

A reader of a new configuration's scopes is then a new file, with no edit
here.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, program_trace, spec, tracing, traffic
from bench.models import common

#: window steps traced in a ``--trace 1`` run
TRACE_STEPS = 5


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _devices(chips: int, allow_cpu: bool):
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def _check_arch(arch, config: Dict[str, Any]) -> None:
    """The program's configuration has to be the file's, key by key."""
    bad = []
    for attr, key in config["program_fields"].items():
        got = arch
        for part in attr.split("."):
            got = getattr(got, part)
        if got != config[key]:
            bad.append(f"{attr}={got!r} but {key}={config[key]!r}")
    if bad:
        raise spec.SpecError(f"{arch.name} differs from its file: "
                             + "; ".join(bad))


def _check_layout(ours, theirs) -> None:
    a = jax.tree.map(lambda x: (x.shape, x.dtype), ours)
    b = jax.tree.map(lambda x: (x.shape, x.dtype), theirs)
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or a != b:
        raise spec.SpecError("the reference's parameters do not match the "
                             "trainer's layout")


def weights_key(seed: int):
    return jax.random.PRNGKey(
        int(np.random.SeedSequence([seed, 2]).generate_state(1)[0]))


def _loader_class():
    from repro.data.pipeline import PackedLoader

    class Loader(PackedLoader):
        """The program's loader, with the benchmark's span around each batch
        and a copy of the first batches it hands out (the checked steps')."""

        def __init__(self, dc, corpus, keep: int):
            super().__init__(dc, corpus=corpus)
            self.keep = keep
            self.fed: List[Dict[str, np.ndarray]] = []

        def batch(self, step, rank=0, n_ranks=1):
            with jax.profiler.TraceAnnotation("loader.batch"):
                out = super().batch(step, rank, n_ranks)
            if len(self.fed) < self.keep:
                self.fed.append({k: np.array(v) for k, v in out.items()})
            return out

    return Loader


def log(t_start: float, msg: str) -> None:
    print(f"[bench {time.perf_counter() - t_start:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def _train_one(trainer) -> None:
    with jax.profiler.TraceAnnotation(tracing.STEP_SPAN):
        trainer.train(1)


class Session:
    """The program's trainer for one cell, built once, and the reference
    beside it.  ``start`` gives the trainer a seed's weights, documents and
    a fresh optimizer state; ``checked`` drives its first steps."""

    def __init__(self, cell: spec.Cell, seconds: float, peaks: Dict):
        from repro import compile_cache
        from repro.configs import registry
        from repro.launch import train as train_launch
        from repro.runtime.trainer import Trainer

        compile_cache.enable_jax_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.config, self.mix = cell.config, cell.traffic
        self.ref = cell.reference()
        _check_arch(registry.get_arch(self.config["program_arch"]),
                    self.config)
        self.rows, self.seq = self.mix["rows"], self.mix["seq_len"]
        self.n_checked = self.config["checked_steps"]
        self.flops_step = (self.ref.flops_per_token(self.config, self.seq)
                           * self.rows * self.seq)
        # no run can outpace the chip's peak, so this many steps suffice
        self.max_steps = self.n_checked + TRACE_STEPS + 2 + math.ceil(
            seconds * peaks["bf16_flops_per_s"] / self.flops_step)
        args = train_launch.parse_args(
            ["--arch", self.config["program_arch"], "--batch",
             str(self.rows), "--seq", str(self.seq)])
        cfg, self.dc, tc = train_launch.trainer_config(args)
        for k in ("lr", "warmup", "total_steps"):
            if getattr(tc, k) != self.config["training"][k]:
                raise spec.SpecError(
                    f"the trainer's {k} is {getattr(tc, k)}, its file says "
                    f"{self.config['training'][k]}")
        self.trainer = Trainer(cfg, self.dc, tc)
        self.make_weights = jax.jit(
            lambda k: self.ref.init_params(self.config, k))
        self.diff_norms = jax.jit(lambda a, b: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    def start(self, seed: int) -> traffic.Documents:
        docs = traffic.Documents(self.mix, self.config["vocab_size"], seed,
                                 self.max_steps * self.rows)
        t = self.trainer
        t.loader = _loader_class()(self.dc, traffic.Corpus(docs),
                                   self.n_checked)
        params0 = self.make_weights(weights_key(seed))
        _check_layout(params0, t.state.params)
        if int(t.state.step) != 0:  # a state that has trained: new moments
            t.state = t.state._replace(opt_state=None)
            t.state = t.state._replace(opt_state=t.optimizer.init(params0),
                                       step=jnp.zeros((), jnp.int32))
        t.state = t.state._replace(params=params0)
        self.seed = seed
        gc.collect()
        return docs

    def checked(self) -> Dict[str, Any]:
        """The first steps through the trainer's own ``train`` call: each
        step's loss, the first clipped gradient as the optimizer got it
        (its first moment over 1 - b1), and the parameters' change.  The
        starting weights are made again for that, rather than kept through
        steps whose program leaves little room on the chip."""
        t = self.trainer
        first = len(t.history)
        b1 = self.config["training"]["b1"]
        out: Dict[str, Any] = {}
        for k in range(self.n_checked):
            _train_one(t)
            if k == 0:
                out["first_grad"] = [g / (1 - b1) for g in
                                     common.leaf_norms(t.state.opt_state["m"])]
        params0 = self.make_weights(weights_key(self.seed))
        out["change"] = [float(x) for x in jax.device_get(
            self.diff_norms(t.state.params, params0))]
        del params0
        out["losses"] = [h["loss"]
                         for h in t.history[first:first + self.n_checked]]
        out["fed"] = t.loader.fed
        return out

    def batches(self, docs: traffic.Documents) -> List[Dict[str, np.ndarray]]:
        return [traffic.pack_rows(docs, range(k * self.rows,
                                              (k + 1) * self.rows))
                for k in range(self.n_checked)]

    def reference(self, seed: int, batches, ops=None) -> Dict:
        return common.train_steps(
            self.ref.block_loss, self.config,
            self.make_weights(weights_key(seed)), batches,
            ops or common.Ops())


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root=None, allow_cpu: bool = False) -> Dict[str, Any]:
    """One run; returns the result line.  ``allow_cpu`` is for tests."""
    cell = spec.load_cell(workload, root)
    devs = _devices(cell.chips, allow_cpu)
    dev = devs[0]
    peaks = spec.peaks(dev.device_kind, cell.root)
    ses = Session(cell, seconds, peaks)
    log(t_start, f"{workload}: trainer built on {dev.device_kind}")
    docs = ses.start(seed)
    log(t_start, "weights and documents made")
    prog = ses.checked()
    trainer = ses.trainer
    log(t_start, f"{ses.n_checked} checked steps done, losses "
                 f"{prog['losses']}")

    # ---- the window ----
    result: Dict[str, Any] = {}
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python calls: only overhead here
        jax.profiler.start_trace(logdir, profiler_options=options)
    setup_s = time.perf_counter() - t_start
    first = len(trainer.history)
    t0 = time.perf_counter()
    while True:
        _train_one(trainer)
        n = len(trainer.history) - first
        if (n >= TRACE_STEPS) if trace else (time.perf_counter() - t0
                                             >= seconds):
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window = trainer.history[first:]
    log(t_start, f"window: {len(window)} steps in {window_s:.3f}s")
    failed = sum(1 for h in window if not math.isfinite(h["loss"]))
    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    if trace:
        hlo_text, step_memory = _compile_step(trainer, t_start)
    del trainer
    ses.trainer = None
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    if trace:
        xplane = tracing.find_xplane(logdir)
        red = tracing.reduce(tracing.load(xplane, _device_lines(dev)))
        program = _program_trace(xplane, hlo_text, dev, t_start)
        shutil.rmtree(logdir, ignore_errors=True)
        ctx = {"flops_per_step": ses.flops_step, "peaks": peaks,
               "reduced": red, "program": program, "ref": ses.ref,
               "config": ses.config, "traffic": ses.mix,
               "tokens_per_step": ses.rows * ses.seq}
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if step_memory is not None:
            device["step_memory_bytes"] = step_memory
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = {"device_ops": red.device_ops,
                                   "idle_gaps": red.idle_gaps}
            if program is not None:
                idle = sorted(program_trace.idle_by_span(program).items(),
                              key=lambda kv: -kv[1])[:tracing.TOP]
                result["breakdown"].update(
                    scopes=program_trace.by_scope(program),
                    idle_by_span=[[n, t] for n, t in idle])
    else:
        tokens = len(window) * ses.rows * ses.seq
        metrics = {"train_tokens_per_s": {"value": tokens / window_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}

    # ---- the reference, over the same weights and documents ----
    batches = ses.batches(docs)
    prog["batch"] = check.batch_mismatch(prog.pop("fed"), batches)
    theirs = ses.reference(seed, batches)
    log(t_start, f"reference done, losses {theirs['losses']}")
    checks = check.judge(check.readings(prog, theirs), cell.limits)
    return {"correct": failed == 0 and check.passed(checks),
            "attempted": len(window), "failed": failed, "metrics": metrics,
            "device": device, **result, "checks": checks}


def _compile_step(trainer, t_start: float
                  ) -> Tuple[Optional[str], Optional[Dict[str, int]]]:
    """The step's HLO text and memory analysis from one fresh compile after
    the traced window; (None, None), logged, where it fails: the traced
    run's other readings do not need it."""
    t0 = time.perf_counter()
    try:
        step = program_trace.compile_step(trainer)
        out = step.as_text(), program_trace.memory_bytes(
            step.memory_analysis())
    except Exception:
        log(t_start, "the step's fresh compile failed; no program trace\n"
            + traceback.format_exc())
        return None, None
    log(t_start, f"step compiled afresh in {time.perf_counter() - t0:.3f}s, "
                 f"memory {out[1]}")
    return out


def _program_trace(xplane: str, hlo_text: Optional[str], dev, t_start: float
                   ) -> Optional[program_trace.ProgramTrace]:
    """The program's spans and scoped device operations, or None, logged,
    where there is no step HLO or the trace does not load."""
    if hlo_text is None:
        return None
    try:
        return program_trace.load(xplane, hlo_text, _device_lines(dev))
    except Exception:
        log(t_start, "the program trace did not load\n"
            + traceback.format_exc())
        return None


def _device_lines(dev):
    if dev.platform == "tpu":
        return tracing.tpu_ops
    # a test's CPU run: XLA's CPU ops carry the ``hlo_op`` stat on host
    # threads; they stand in for device operations there
    return lambda plane, line: plane == "/host:CPU" and "XLAPjRt" in line


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
