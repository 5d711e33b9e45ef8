"""Fault-tolerant, plan-aware production GAN training loop.

This is the training-side counterpart of the serving engine: where
``serve/gan_engine.py`` turns compiled :class:`~repro.kernels.plan.TconvPlan`s
into a request-serving system, :class:`GanTrainer` turns the jointly-tuned
G+D step plans into a **long-running job that survives the failure model**
documented in :mod:`repro.distributed.fault_tolerance`:

* **step-atomic checkpoint/resume** — ``train/checkpoint.py``'s temp-file +
  ``os.replace`` npz every ``ckpt_every`` steps (+ at SIGTERM and at exit).
  Because every input of step ``t`` is a pure function of (state, ``t``) —
  data via ``data.batch(index)``, latents via ``fold_in(z_seed, index)``,
  LR via the optimizer ``count`` — a killed job relaunched with the same
  command line resumes with a **bit-exact loss trajectory** (the chaos
  suite and the ``training`` benchmark gate both prove this).
* **SIGTERM = preemption** — the handler only sets a flag; the loop
  finishes the in-flight step, checkpoints, and returns cleanly.
* **NaN/anomaly guard** — the fused step computes both updates, then a
  single finiteness predicate selects (inside jit, so donation is safe)
  between the new trees and the old ones: a non-finite step leaves params,
  optimizer state, and error-feedback state **bitwise untouched** and is
  counted in ``metrics["skipped_steps"]`` (which itself rides in the
  checkpoint, so the count survives restarts).
* **data parallelism** — the generator runs through
  :func:`~repro.distributed.sharding.shard_plan_apply` (batch sharded over
  the ``(pod, data)`` mesh axes, no-op without a mesh), so the same trainer
  drives single-device tests and the multi-pod mesh.
* **int8 gradient compression + error feedback** — ``compress_grads=True``
  routes the accumulated gradients through
  :func:`~repro.optim.compression.error_feedback_compress`; the error
  state is carried **inside the checkpointed optimizer state**, so the
  compressor's memory survives crash/resume bit-exactly.
* **elastic degradation** — ``pods_alive < pods_total`` feeds
  :func:`~repro.distributed.fault_tolerance.elastic_batch_schedule`: the
  per-step microbatch shrinks with the alive fraction and gradient
  accumulation (a ``lax.scan`` inside the one fused step) makes up the
  effective batch. The step plan is compiled at the *micro* batch size, so
  a re-scale recompiles exactly one plan.

The step itself is the GAN alternation from ``examples/train_dcgan.py``
(non-saturating loss, AdamW for both nets, D update then G update against
the updated D), fused into ONE jitted function that closes over the
compiled train plan — no per-call dispatch, autotune-cache consult, or
Python-level optimizer logic inside the loop.

Failure injection for all of the above lives in
:mod:`repro.train.fault_injection`; the response matrix is documented in
``docs/TRAINING.md``.
"""
from __future__ import annotations

import dataclasses
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.fault_tolerance import elastic_batch_schedule
from repro.distributed.sharding import shard_plan_apply
from repro.models import gan
from repro.obs import trace as obs
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import error_feedback_compress, zero_error_state
from repro.timing import StepTimer
from repro.train.checkpoint import (
    device_put_like,
    gc_checkpoints,
    restore_checkpoint,
    save_checkpoint,
)

@dataclasses.dataclass(frozen=True)
class GanTrainerConfig:
    """Static trainer configuration (everything the fused step closes over)."""

    global_batch: int = 8
    opt: AdamWConfig = dataclasses.field(
        default_factory=lambda: AdamWConfig(
            lr=2e-4, b1=0.5, b2=0.999, weight_decay=0.0
        )
    )
    ckpt_every: int = 50
    keep_last: int = 3
    log_every: int = 20
    method: str = "auto"        # plan resolution (see kernels/plan.py)
    dtype: str = "float32"
    z_seed: int = 7
    compress_grads: bool = False  # int8 + error feedback (cross-pod DP)
    pods_alive: int = 1
    pods_total: int = 1
    data_parallel: bool = True    # shard_plan_apply when a mesh is active

    def __post_init__(self):
        if not (1 <= self.pods_alive <= self.pods_total):
            raise ValueError(
                f"need 1 <= pods_alive <= pods_total, got "
                f"{self.pods_alive}/{self.pods_total}"
            )
        if self.global_batch < 1:
            raise ValueError(f"global_batch must be >= 1, got "
                             f"{self.global_batch}")

    @property
    def micro_accum(self) -> tuple:
        """(per-step microbatch, accumulation steps) under the elastic
        schedule — ``(global_batch, 1)`` with all pods alive."""
        return elastic_batch_schedule(
            self.global_batch, self.pods_alive, self.pods_total
        )


class GanTrainer:
    """Plan-aware fault-tolerant GAN trainer (see module docstring).

    ``data.batch(index) -> (micro, H, W, C)`` must be a pure function of
    ``index`` (e.g. :class:`repro.data.SyntheticImages` at the micro batch
    size) — that purity is what makes restarts and elastic re-shards
    bit-exact. ``hooks`` is an optional object with an
    ``on_step_start(step)`` callback — the seam the fault-injection
    harness drives; production runs pass nothing.
    """

    def __init__(self, cfg, tcfg: GanTrainerConfig, data, *,
                 ckpt_dir=None, hooks=None, log_fn=print, recorder=None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.data = data
        self.ckpt_dir = str(ckpt_dir) if ckpt_dir is not None else None
        self.hooks = hooks
        self.log = log_fn
        self.recorder = recorder   # optional obs FlightRecorder
        self.micro, self.accum = tcfg.micro_accum
        # the jointly-tuned whole-generator step plan, compiled ONCE at the
        # micro batch size, before the step is traced
        self.train_plan = gan.generator_plan(
            cfg, self.micro, train=True, method=tcfg.method,
        )
        self.out_hw = cfg.out_hw(cfg.layers[-1][0])
        self.out_c = cfg.layers[-1][2]
        self.skipped_steps = 0
        self.resumed_step = None
        self.timer = StepTimer()
        self._stop = False
        self._step_fn = jax.jit(self._build_step(), donate_argnums=(0,))
        self._z_key = jax.random.key(tcfg.z_seed)
        self._inputs_fn = jax.jit(self._build_inputs())

    # ------------------------------------------------------------- state

    def init_state(self, key) -> dict:
        kg, kd = jax.random.split(key)
        gp = gan.generator_init(kg, self.cfg)
        dp = gan.discriminator_init(kd, self.out_hw, self.out_c)
        g_opt = adamw_init(gp, self.tcfg.opt)
        d_opt = adamw_init(dp, self.tcfg.opt)
        if self.tcfg.compress_grads:
            g_opt["err"] = zero_error_state(gp)
            d_opt["err"] = zero_error_state(dp)
        return {"g_params": gp, "d_params": dp,
                "g_opt": g_opt, "d_opt": d_opt}

    # ---------------------------------------------------------- the step

    def _generate(self, gp, z):
        if self.tcfg.data_parallel:
            return shard_plan_apply(
                lambda p, zz, plan: gan.generator_apply(
                    p, self.cfg, zz, plan=plan
                ),
                gp, z, self.train_plan,
            )
        return gan.generator_apply(gp, self.cfg, z, plan=self.train_plan)

    def _build_step(self):
        cfg_t = self.tcfg
        opt_cfg = cfg_t.opt

        def d_loss(dp, gp, real, z):
            fake = self._generate(gp, z)
            d_real = gan.discriminator_apply(dp, real)
            d_fake = gan.discriminator_apply(dp, fake)
            return (jnp.mean(jax.nn.softplus(-d_real))
                    + jnp.mean(jax.nn.softplus(d_fake)))

        def g_loss(gp, dp, z):
            fake = self._generate(gp, z)
            return jnp.mean(
                jax.nn.softplus(-gan.discriminator_apply(dp, fake))
            )

        def accumulate(loss_fn, wrt_params, reals, zs):
            """Mean loss and mean grads (wrt ``wrt_params``) over the accum
            microbatches, via a scan-carried fp32 accumulator (constant
            trace size in accum). ``loss_fn(params, real, z)``."""
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), wrt_params
            )

            def one(carry, xz):
                acc_l, acc_g = carry
                real, z = xz
                l, g = jax.value_and_grad(loss_fn)(wrt_params, real, z)
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g
                )
                return (acc_l + l, acc_g), None

            (tot_l, tot_g), _ = jax.lax.scan(
                one, (jnp.zeros((), jnp.float32), zeros), (reals, zs)
            )
            n = reals.shape[0]
            mean_g = jax.tree_util.tree_map(lambda g: g / n, tot_g)
            return tot_l / n, mean_g

        def maybe_compress(grads, opt_state):
            if not cfg_t.compress_grads:
                return grads, None
            return error_feedback_compress(grads, opt_state["err"])

        def step_fn(state, reals, zs):
            gp, dp = state["g_params"], state["d_params"]
            g_opt, d_opt = state["g_opt"], state["d_opt"]

            # --- D phase: accumulate over micros, update against current G
            dl, dgrads = accumulate(
                lambda dpp, real, z: d_loss(dpp, gp, real, z),
                dp, reals, zs,
            )
            dgrads, d_err = maybe_compress(dgrads, d_opt)
            dp_new, d_opt_new, d_gnorm = adamw_update(
                dgrads, d_opt, dp, opt_cfg, opt_cfg.lr
            )

            # --- G phase: against the UPDATED discriminator
            gl, ggrads = accumulate(
                lambda gpp, real, z: g_loss(gpp, dp_new, z),
                gp, reals, zs,
            )
            ggrads, g_err = maybe_compress(ggrads, g_opt)
            gp_new, g_opt_new, g_gnorm = adamw_update(
                ggrads, g_opt, gp, opt_cfg, opt_cfg.lr
            )

            if cfg_t.compress_grads:   # err rides inside the opt state
                d_opt_new = dict(d_opt_new, err=d_err)
                g_opt_new = dict(g_opt_new, err=g_err)

            # --- anomaly guard: ONE step-atomic predicate for both nets.
            # A non-finite loss or grad norm anywhere selects the OLD trees
            # everywhere (params, opt moments, count, error feedback) —
            # inside jit, so it composes with buffer donation.
            ok = (jnp.isfinite(dl) & jnp.isfinite(gl)
                  & jnp.isfinite(d_gnorm) & jnp.isfinite(g_gnorm))

            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )

            new_state = {
                "g_params": sel(gp_new, gp),
                "d_params": sel(dp_new, dp),
                "g_opt": sel(g_opt_new, g_opt),
                "d_opt": sel(d_opt_new, d_opt),
            }
            metrics = {
                "g_loss": gl.astype(jnp.float32),
                "d_loss": dl.astype(jnp.float32),
                "g_gnorm": g_gnorm.astype(jnp.float32),
                "d_gnorm": d_gnorm.astype(jnp.float32),
                "skipped": (~ok).astype(jnp.int32),
            }
            return new_state, metrics

        return step_fn

    # ------------------------------------------------------------ inputs

    def _build_inputs(self):
        accum, z_shape = self.accum, (self.micro, self.cfg.z_dim)

        def inputs_fn(key, micros, step):
            """Stack the ``accum`` microbatches and draw their latents,
            ``normal(fold_in(key, step * accum + j))``; ``step`` is traced,
            so one executable serves every step."""
            zs = [jax.random.normal(jax.random.fold_in(key, step * accum + j),
                                    z_shape)
                  for j in range(accum)]
            return jnp.stack(micros), jnp.stack(zs)

        return inputs_fn

    def _batches(self, step: int):
        """The step's stacked (accum, micro, ...) inputs, each microbatch a
        pure function of its flat index ``step * accum + j``: the data
        source's batches, then one jitted call for the stack and latents."""
        micros = tuple(self.data.batch(step * self.accum + j)
                       for j in range(self.accum))
        return self._inputs_fn(self._z_key, micros, step)

    # ------------------------------------------------------- checkpoints

    def _save(self, step: int, state: dict) -> None:
        save_checkpoint(
            self.ckpt_dir, step,
            {"g": state["g_params"], "d": state["d_params"]},
            {"g": state["g_opt"], "d": state["d_opt"]},
            extra={"skipped_steps": np.int64(self.skipped_steps)},
        )
        gc_checkpoints(self.ckpt_dir, self.tcfg.keep_last)

    def resume(self, state: dict):
        """Restore the newest valid checkpoint into ``state``'s placement.

        Returns ``(start_step, state)`` — ``(0, state)`` untouched when no
        checkpoint loads. Restored host arrays are ``device_put`` with the
        LIVE tree's shardings, so an elastic restart re-shards here."""
        if self.ckpt_dir is None:
            return 0, state
        got, p, o, extra = restore_checkpoint(self.ckpt_dir, log_fn=self.log)
        if got is None:
            return 0, state
        state = {
            "g_params": device_put_like(p["g"], state["g_params"]),
            "d_params": device_put_like(p["d"], state["d_params"]),
            "g_opt": device_put_like(o["g"], state["g_opt"]),
            "d_opt": device_put_like(o["d"], state["d_opt"]),
        }
        if extra is not None and "skipped_steps" in extra:
            self.skipped_steps = int(extra["skipped_steps"])
        self.resumed_step = got
        return got, state

    # ---------------------------------------------------------- the loop

    def _install_sigterm(self):
        def handler(signum, frame):
            self._stop = True  # checkpoint + exit at the next step boundary

        try:
            return signal.signal(signal.SIGTERM, handler)
        except ValueError:
            return None  # not in main thread (tests)

    def run(self, state, *, steps: int):
        """Train to ``steps`` total steps (resuming first), returning
        ``(state, history)`` with one history row per executed step:
        ``{"step", "g_loss", "d_loss", "skipped"}``. Interruptions:
        SIGTERM checkpoints and returns cleanly; a crash (exception) loses
        at most the steps since the last checkpoint."""
        self._stop = False
        prev_handler = self._install_sigterm()
        try:
            step, state = self.resume(state)
            if self.resumed_step is not None:
                self.log(f"[gan-trainer] resuming from step {step}")
            history = []
            t0 = time.time()
            self.timer = StepTimer()
            try:
                while step < steps and not self._stop:
                    with obs.span("train.step", step=step):
                        if self.hooks is not None:
                            self.hooks.on_step_start(step)
                        with obs.span("train.batch", step=step):
                            reals, zs = self._batches(step)
                        with obs.span("train.step_fn", step=step):
                            state, metrics = self._step_fn(state, reals, zs)
                            # the wait for the step, then the copy
                            with obs.span("train.sync", step=step):
                                metrics = jax.device_get(metrics)
                    dt = self.timer.tick()
                    skipped = int(metrics["skipped"])
                    self.skipped_steps += skipped
                    if self.recorder is not None:
                        self.recorder.record(
                            "train.step", step=step, dt=dt, skipped=skipped,
                            g_loss=float(metrics["g_loss"]),
                            d_loss=float(metrics["d_loss"]),
                        )
                    if skipped:
                        if self.recorder is not None:
                            self.recorder.dump(
                                "nan_guard",
                                extra={"step": step,
                                       "skipped_total": self.skipped_steps},
                            )
                        self.log(
                            f"[gan-trainer] step {step}: non-finite step; "
                            f"params untouched (total skipped "
                            f"{self.skipped_steps})"
                        )
                    history.append({
                        "step": step,
                        "g_loss": float(metrics["g_loss"]),
                        "d_loss": float(metrics["d_loss"]),
                        "skipped": skipped,
                    })
                    if step % self.tcfg.log_every == 0:
                        self.log(
                            f"[gan-trainer] step {step} "
                            f"g_loss {float(metrics['g_loss']):.4f} "
                            f"d_loss {float(metrics['d_loss']):.4f} "
                            f"({dt * 1e3:.1f}ms, "
                            f"{time.time() - t0:.1f}s total)"
                        )
                    if (self.ckpt_dir
                            and (step + 1) % self.tcfg.ckpt_every == 0):
                        self._save(step + 1, state)
                    step += 1
            except Exception as e:
                # post-mortem artifact before the crash propagates (covers
                # SimulatedCrash from the fault harness and real faults);
                # the checkpoint story is unchanged — at most the steps
                # since the last save are lost
                if self.recorder is not None:
                    self.recorder.record("crash", step=step,
                                         error=type(e).__name__)
                    self.recorder.dump(
                        f"crash:{type(e).__name__}",
                        extra={"step": step, "error": str(e)},
                    )
                raise

            if self.ckpt_dir and (self._stop or step >= steps):
                self._save(step, state)
                if self._stop:
                    self.log(
                        f"[gan-trainer] SIGTERM: checkpointed step {step}, "
                        "exiting cleanly"
                    )
            if self._stop and self.recorder is not None:
                # after the final save so the dump reflects durable state
                self.recorder.record("sigterm", step=step)
                self.recorder.dump("sigterm", extra={"step": step})
            return state, history
        finally:
            if prev_handler is not None:
                signal.signal(signal.SIGTERM, prev_handler)

    # ----------------------------------------------------------- metrics

    @property
    def stopped(self) -> bool:
        """True when the last run exited on SIGTERM rather than completion."""
        return self._stop

    def metrics_summary(self) -> dict:
        return {
            "skipped_steps": self.skipped_steps,
            "resumed_step": self.resumed_step,
            "micro_batch": self.micro,
            "grad_accum": self.accum,
            "steps_timed": len(self.timer.steps),
            "step_time_s": {
                "mean": self.timer.mean() if self.timer.steps else 0.0,
                "median": self.timer.median() if self.timer.steps else 0.0,
            },
        }
