"""Trainer: the fault-tolerant training loop.

Wires together data pipeline → train_step → async checkpointing →
straggler monitor, with resume-from-latest on construction, so a restart
after preemption (or an elastic re-plan) continues exactly where the dead
run stopped: the data pipeline is addressed by the checkpointed step and
the RNG by a (seed, step) fold — no iterator state to recover.

With ``online_calibrate`` the per-step ``time.perf_counter`` timings also
feed an ``OnlineCalibrator`` (``calibration/online.py``): each step
records (the step's property vector, measured seconds) into the telemetry
sink, the streaming RLS tracks the fit, and a drift event triggers a
refit + straggler-threshold re-anchor.  A ``[calib]`` report line (sample
counts, windowed relative error, drift status, refit epochs) prints every
``log_every`` steps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data.pipeline import DataConfig, PackedLoader
from repro.distributed.plan import Plan
from repro.models import transformer
from repro.obs import metrics as _obs_metrics
from repro.obs import report as _obs_report
from repro.obs import trace as _obs_trace
from repro.optim import optimizers as opt
from repro.runtime import steps
from repro.runtime.straggler import StragglerMonitor

_STEP_SECONDS = _obs_metrics.REGISTRY.histogram(
    "repro_train_step_seconds", "measured trainer step wall seconds")


@dataclass
class TrainerConfig:
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    log_every: int = 10
    seed: int = 0
    lr: float = 3e-4
    warmup: int = 20
    total_steps: int = 1000
    async_ckpt: bool = True
    save_on_exit: bool = True  # False simulates preemption mid-interval
    # --- online calibration (calibration/online.py) ---
    online_calibrate: bool = False
    calib_device: Optional[str] = None      # registry name for refit models
    calib_registry: Optional[str] = None    # registry dir override
    calib_auto_register: bool = False       # write refits into the registry


class Trainer:
    def __init__(self, cfg: ArchConfig, data_cfg: DataConfig,
                 tc: TrainerConfig, plan: Optional[Plan] = None,
                 predicted_step_s: Optional[float] = None,
                 calibrator=None, injector=None):
        self.cfg = cfg
        self.tc = tc
        # optional FaultInjector (runtime/faults.py); every hook below is
        # behind `is not None`, so the hot path is untouched when chaos
        # is off
        self.injector = injector
        self.loader = PackedLoader(data_cfg)
        self.optimizer = opt.get_optimizer(cfg.optimizer)
        lr = opt.warmup_cosine(tc.lr, tc.warmup, tc.total_steps)
        plan = plan or Plan(dp_axes=())
        self.step_fn = jax.jit(steps.make_train_step(
            cfg, self.optimizer, plan, lr_schedule=lr))
        self.state = steps.init_train_state(
            cfg, jax.random.PRNGKey(tc.seed), self.optimizer)
        self.monitor = StragglerMonitor(
            n_hosts=1, predicted_step_s=predicted_step_s or 1.0)
        self.ckpt = (store.AsyncCheckpointer(tc.ckpt_dir, tc.keep_ckpts)
                     if tc.ckpt_dir and tc.async_ckpt else None)
        self.history: List[Dict[str, float]] = []

        # ---- online calibration ----
        self.calibrator = calibrator
        if self.calibrator is None and tc.online_calibrate:
            from repro.calibration.online import OnlineCalibrator
            self.calibrator = OnlineCalibrator(
                None, device=tc.calib_device or f"{cfg.name}-online",
                registry_dir=tc.calib_registry,
                auto_register=tc.calib_auto_register)
        self._step_pv = None
        if self.calibrator is not None:
            # the live step's property vector: this trainer runs the whole
            # batch on the local substrate, so the pv is the single-device
            # cell of (cfg × the ACTUAL data workload × the jitted plan)
            from repro.core import predictor
            from repro.core.workload import WorkloadSpec
            live = WorkloadSpec(phase="train",
                                global_batch=data_cfg.global_batch,
                                seq_len=data_cfg.seq_len,
                                name="train_live")
            self._step_pv = predictor.plan_property_vector(
                cfg, live, plan, {"data": 1})

        # ---- resume (newest VALID checkpoint: an invalid one — e.g. a
        # write the preemption itself interrupted — is quarantined and
        # the next-older step restored instead of crashing the restart)
        if tc.ckpt_dir:
            restored = store.restore_latest_valid(tc.ckpt_dir, self.state)
            if restored is not None:
                self.state, _, latest = restored
                _obs_report.emit("trainer", text=f"resumed from step "
                                                 f"{latest}")

    # ------------------------------------------------------------------
    @property
    def step(self) -> int:
        return int(self.state.step)

    def _save(self, blocking: bool = False):
        if not self.tc.ckpt_dir:
            return
        if self.ckpt is not None and not blocking:
            self.ckpt.save(self.step, self.state)
        else:
            if self.ckpt is not None:
                self.ckpt.wait()
            store.save(self.tc.ckpt_dir, self.step, self.state)
            store.prune(self.tc.ckpt_dir, self.tc.keep_ckpts)

    def train(self, n_steps: int,
              on_metrics: Optional[Callable[[int, Dict], None]] = None
              ) -> List[Dict[str, float]]:
        tracer = _obs_trace.get_tracer()
        for _ in range(n_steps):
            # the profiler's step; its children name what the host does
            # while the device waits (docs/OBSERVABILITY.md)
            with tracer.span("train.iter", profiler_step=True) as it:
                with tracer.span("train.sync"):
                    step = self.step
                it.set(step_num=step)
                if self.injector is not None:
                    # may corrupt state files or raise DeviceLossError —
                    # BEFORE the step runs, so the supervisor resumes at
                    # exactly this step and batch semantics stay exact
                    self.injector.step_begin(step)
                with tracer.span("train.batch"):
                    batch = {k: jnp.asarray(v)
                             for k, v in self.loader.batch(step).items()}
                # the model's prediction for THIS step — the straggler
                # monitor carries it (re-anchored on every refit), so the
                # span's predicted overlay tracks the live model, not the
                # launch one
                pred_s = self.monitor.predicted_step_s
                t0 = time.perf_counter()
                with tracer.span("train_step", predicted_s=pred_s,
                                 step=step):
                    with tracer.span("train.dispatch"):
                        self.state, metrics = self.step_fn(self.state, batch)
                    with tracer.span("train.wait"):
                        jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                if self.injector is not None:
                    # scheduled slowdowns/spikes scale the OBSERVED time:
                    # the monitor, watchdog, histogram and calibrator all
                    # see the same perturbed measurement, as they would a
                    # real straggler
                    dt = self.injector.perturb_step_time(step, dt)
                _STEP_SECONDS.observe(dt)
                self.monitor.observe(step, [dt])
                if self.calibrator is not None:
                    with tracer.span("train.calib"):
                        sample = dt
                        if self.injector is not None:
                            sample = self.injector.perturb_telemetry(step,
                                                                     dt)
                        ev = self.calibrator.observe(
                            self._step_pv, sample, step=step, tag="train",
                            phase="train")
                        if ev is not None:
                            # refit already happened inside observe();
                            # re-anchor the straggler threshold to the
                            # refit model's prediction
                            self.monitor.reanchor(
                                self.calibrator.model.predict(
                                    self._step_pv))
                            _obs_report.emit(
                                "calib",
                                text=f"drift detected at step {step} "
                                     f"(direction={ev.direction}, onset "
                                     f"seq {ev.onset_seq}): refit epoch "
                                     f"{self.calibrator.refits}, revision "
                                     f"{self.calibrator.revision}")

                with tracer.span("train.pull"):
                    m = {"step": step, "loss": float(metrics["loss"]),
                         "grad_norm": float(metrics["grad_norm"]),
                         "lr": float(metrics["lr"]), "time_s": dt}
                self.history.append(m)
                if on_metrics:
                    on_metrics(step, m)
                elif step % self.tc.log_every == 0:
                    _obs_report.emit(
                        "trainer",
                        text=f"step {step:5d} loss {m['loss']:.4f} "
                             f"gnorm {m['grad_norm']:.3f} {dt*1e3:.0f}ms")
                if self.calibrator is not None \
                        and step % self.tc.log_every == 0:
                    _obs_report.emit("calib",
                                     text=self.calibrator.report_line())
                if self.tc.ckpt_dir and (step + 1) % self.tc.ckpt_every == 0:
                    with tracer.span("train.ckpt"):
                        self._save()
        if self.tc.ckpt_dir and self.tc.save_on_exit:
            with tracer.span("train.ckpt"):
                self._save(blocking=True)
        elif self.ckpt is not None:
            with tracer.span("train.ckpt"):
                self.ckpt.wait()
        return self.history
