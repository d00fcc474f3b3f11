"""Fault-tolerant training loop with run-time AT integration.

Large-scale behaviours implemented here and exercised by tests:

* **checkpoint/restart** — atomic saves every N steps; on start the loop
  restores the latest checkpoint and replays the data stream from that step
  (the dataset is pure in (seed, step)), so an interrupted run converges to
  bit-identical losses (test_runtime.py asserts this).
* **failure injection** — ``failure_hook(step)`` may raise
  :class:`SimulatedFailure`; ``run()`` treats it exactly like a node loss:
  tear down step state, restore, continue.
* **straggler mitigation = FIBER run-time AT** — the jitted train step for
  every microbatch degree is AOT-precompiled (ppOpen-AT's pre-generated
  subroutines); a :class:`repro.core.tuner.RuntimeSelector` watches measured
  step times and re-selects the next-best precompiled degree when the
  current one regresses ≥ tolerance — a free switch, as the paper's Fig-12
  measures for ``omp_set_num_threads``.
* **gradient accumulation degree** — the PP: the global batch is split into
  ``n_microbatches`` scanned chunks; more microbatches = less activation
  memory, more sequential steps (the thread-grain trade, docs/design.md §2).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import (
    ATRegion,
    AutotunedOp,
    BasicParams,
    KernelSpec,
    ParamSpace,
    PerfParam,
    ProgramMember,
    ProgramResult,
    ProgramSpec,
    TuningDB,
    register_kernel,
)
from repro.models import param_specs, train_loss
from repro.models.config import ModelConfig
from repro.models.spec import init_params
from repro.optim import AdamWConfig, adamw_init, adamw_update


class SimulatedFailure(RuntimeError):
    """Stand-in for a node loss / preemption in tests and drills."""


@dataclass
class TrainLoopConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    save_every: int = 50
    keep_checkpoints: int = 3
    n_microbatches: int = 1
    microbatch_candidates: Sequence[int] = (1, 2, 4)
    straggler_tolerance: float = 3.0
    seed: int = 0
    # whole-program joint AT (docs/program.md): tune (microbatch degree ×
    # remat directive) against the *measured full train step* before the
    # loop starts, instead of pinning the configured degree.  The two knobs
    # are the paper's pair — remat is the directive change, the microbatch
    # degree the thread-count analogue — and they interact (both trade
    # activation memory against time), which is why they are tuned jointly.
    joint_tune: bool = False
    joint_cap: Optional[int] = 16
    joint_k: Optional[int] = None
    remat_candidates: Sequence[str] = ("none", "full")
    # fleet device keying (docs/fleet.md): namespace the train step's BP —
    # and the joint program fingerprint — under the host's
    # DeviceFingerprint, so a fleet-shared TuningDB never hands this host a
    # degree/remat winner measured on different hardware.
    device_key: bool = False


def make_train_step(
    cfg: ModelConfig, opt_cfg: AdamWConfig, n_microbatches: int
) -> Callable:
    """Build the pure train step for one microbatch degree (named so the
    profiler names its program ``jit_train_step``)."""

    def train_step(params, opt_state, batch):
        if n_microbatches == 1:
            loss, grads = jax.value_and_grad(lambda p: train_loss(p, batch, cfg))(
                params
            )
        else:
            def split(x):
                b = x.shape[0]
                if x.ndim >= 2 and x.shape[0] == 3 and b == 3:  # mrope positions
                    return None
                return x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])

            # positions (3, B, S) needs batch-axis split on axis 1
            def split_leaf(path_x):
                return path_x

            micro = {}
            for k, v in batch.items():
                if k == "positions" and v.ndim == 3 and v.shape[0] == 3:
                    micro[k] = jnp.moveaxis(
                        v.reshape(3, n_microbatches, -1, v.shape[-1]), 1, 0
                    )
                else:
                    micro[k] = v.reshape(
                        (n_microbatches, v.shape[0] // n_microbatches) + v.shape[1:]
                    )

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                g_acc, loss_acc = carry
                loss, grads = jax.value_and_grad(
                    lambda p: train_loss(p, mb, cfg)
                )(params)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                )
                return (g_acc, loss_acc + loss), None

            (gsum, losssum), _ = jax.lax.scan(body, (zeros, jnp.float32(0.0)), micro)
            grads = jax.tree.map(lambda g: g / n_microbatches, gsum)
            loss = losssum / n_microbatches
        params, opt_state, metrics = adamw_update(grads, opt_state, params, opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        opt_cfg: AdamWConfig,
        loop_cfg: TrainLoopConfig,
        tuning_db: Optional[TuningDB] = None,
    ) -> None:
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.loop = loop_cfg
        self.db = tuning_db or TuningDB()
        self.ckpt = (
            CheckpointManager(
                loop_cfg.ckpt_dir, loop_cfg.save_every, loop_cfg.keep_checkpoints
            )
            if loop_cfg.ckpt_dir
            else None
        )
        self.straggler_events = 0
        self.restarts = 0

        # The train step is a registry op like any kernel: the microbatch
        # degree is its PP (run-time layer), and its shape class is fixed by
        # (arch, candidate degrees).  The configured degree is pinned rather
        # than wall-clock-tuned so restarted runs stay bit-deterministic;
        # joint_tune replaces the pin with a whole-program search whose cost
        # is the measured full step.  The remat directive lives in a mutable
        # cell so a joint winner hot-applies without rebuilding the region
        # (the region is invalidated instead, see _on_joint_apply).  The
        # live step donates params and optimizer state — the loop only ever
        # keeps the step's outputs — so a step holds one copy of them.
        degrees = tuple(loop_cfg.microbatch_candidates)
        self._step_remat = cfg.remat
        bp = BasicParams.make(arch=cfg.name, kind="train_runtime", micro=degrees)
        if loop_cfg.device_key:
            from repro.fleet.fingerprint import device_bp_entries

            bp = bp.with_entries(**device_bp_entries())
        spec = register_kernel(
            KernelSpec(
                name=f"train_step/{cfg.name}",
                make_region=lambda _bp: ATRegion(
                    name="train_step",
                    space=ParamSpace([PerfParam("n_micro", degrees)]),
                    instantiate=lambda pt: jax.jit(
                        make_train_step(
                            cfg.with_(remat=self._step_remat), opt_cfg,
                            pt["n_micro"],
                        ),
                        donate_argnums=(0, 1),
                    ),
                ),
                shape_class=lambda *a, **k: bp,
                tags=("runtime",),
            ),
            replace=True,
        )
        self.op = AutotunedOp(
            spec,
            db=self.db,
            tune=False,
            warm=False,
            monitor=False,  # the loop times steps itself (it also tracks
            # straggler_events), feeding the selector directly
            tolerance=loop_cfg.straggler_tolerance,
        )
        self.bp = bp
        self._state = self.op.select({"n_micro": loop_cfg.n_microbatches})
        self.region = self._state.region
        self.joint_result: Optional[ProgramResult] = None

    # -- whole-program joint AT (docs/program.md) --------------------------------

    def train_program(self, params, opt_state, batch) -> ProgramSpec:
        """The train step as a joint tuning problem: micro × remat.

        ``micro`` is the live train region (so the joint winner hot-applies
        straight through ``region.select``); ``remat`` is the directive
        member.  The program's cost builds one fresh jitted step per joint
        assignment and measures it end to end — per-knob greedy tuning
        cannot see that both knobs compete for the same activation memory.
        """
        cfg, opt_cfg, loop = self.cfg, self.opt_cfg, self.loop
        remats = tuple(loop.remat_candidates)
        remat_region = ATRegion(
            "train_remat",
            ParamSpace([PerfParam("remat", remats)]),
            instantiate=lambda pt: jax.jit(
                make_train_step(
                    cfg.with_(remat=pt["remat"]), opt_cfg, loop.n_microbatches
                )
            ),
        )
        if cfg.remat in remats:
            remat_region.select({"remat": cfg.remat})  # untuned baseline
        members = [
            ProgramMember("micro", self.region, bp=self.bp),
            ProgramMember(
                "remat", remat_region,
                bp=BasicParams.make(
                    arch=cfg.name, kind="train_remat", remat=remats
                ),
            ),
        ]

        def build(assignment):
            # no donation: the measured thunk re-feeds the same arrays
            step = jax.jit(
                make_train_step(
                    cfg.with_(remat=assignment["remat"]["remat"]),
                    opt_cfg,
                    int(assignment["micro"]["n_micro"]),
                )
            )

            def thunk():
                _, _, metrics = step(params, opt_state, batch)
                return metrics["loss"]

            return thunk

        tokens = batch.get("tokens")
        extra = {
            "arch": cfg.name,
            "backend": jax.default_backend(),
            "batch": int(tokens.shape[0]) if tokens is not None else 0,
            "seq": int(tokens.shape[1]) if tokens is not None else 0,
        }
        if loop.device_key:  # device-namespaced program fingerprint
            from repro.fleet.fingerprint import device_bp_entries

            extra.update(device_bp_entries())
        return ProgramSpec(
            f"train_step/{cfg.name}", members, db=self.db, build=build,
            on_apply=self._on_joint_apply, extra=extra,
        )

    def _on_joint_apply(self, assignment) -> None:
        """Mirror the joint winner's remat directive into the live step.

        The micro member *is* the live region, so its ``select`` already
        landed; the remat directive lives in the instantiate closure, so
        adopting it invalidates the region's compiled candidates (they were
        built under the old directive) — the next step pays one rebuild,
        every later switch is a dict lookup again.
        """
        remat = assignment.get("remat", {}).get("remat")
        if remat is not None and remat != self._step_remat:
            self._step_remat = remat
            self.region.invalidate()

    def joint_tune(self, dataset, key: Optional[jax.Array] = None,
                   force: bool = False,
                   state: Optional[Tuple[Any, Any]] = None) -> ProgramResult:
        """Joint before-execution AT of the whole train step.

        A final winner recorded under the program fingerprint short-circuits
        to a hot apply (zero evaluations, the cross-run cache); otherwise
        the :class:`~repro.core.program.JointSearch` measures full steps.
        ``state`` reuses an already-initialized ``(params, opt_state)`` pair
        (``run()`` passes its own) instead of materializing a second copy.
        """
        key = key if key is not None else jax.random.PRNGKey(self.loop.seed)
        batch = {k: jnp.asarray(v) for k, v in dataset.batch(0).items()}
        params, opt_state = state if state is not None else self.init_state(key)
        program = self.train_program(params, opt_state, batch)
        self.joint_result = program.tune(
            k=self.loop.joint_k, cap=self.loop.joint_cap, force=force
        )
        return self.joint_result

    # -- state ------------------------------------------------------------------

    def init_state(self, key: jax.Array) -> Tuple[Any, Any]:
        params = init_params(key, param_specs(self.cfg))
        opt_state = adamw_init(params, self.opt_cfg)
        return params, opt_state

    # -- main loop ---------------------------------------------------------------

    def run(
        self,
        dataset,
        key: Optional[jax.Array] = None,
        failure_hook: Optional[Callable[[int], None]] = None,
        max_restarts: int = 3,
    ) -> Dict[str, List[float]]:
        key = key if key is not None else jax.random.PRNGKey(self.loop.seed)
        params, opt_state = self.init_state(key)
        if self.loop.joint_tune and self.joint_result is None:
            self.joint_tune(dataset, key, state=(params, opt_state))
        start = 0
        if self.ckpt is not None:
            restored = self.ckpt.restore_latest({"p": params, "o": opt_state})
            if restored is not None:
                start, tree = restored
                params, opt_state = tree["p"], tree["o"]

        selector = self._state.selector
        history: Dict[str, List[float]] = {"loss": [], "step_time": [], "step": []}
        step_times: List[float] = []

        step = start
        while step < self.loop.total_steps:
            try:
                if failure_hook is not None:
                    failure_hook(step)
                batch = {
                    k: jnp.asarray(v) for k, v in dataset.batch(step).items()
                }
                t0 = time.perf_counter()
                params, opt_state, metrics = self.region(params, opt_state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0

                step_times.append(dt)
                if len(step_times) > 32:
                    step_times.pop(0)
                med = float(np.median(step_times))
                if len(step_times) >= 8 and dt > self.loop.straggler_tolerance * med:
                    self.straggler_events += 1
                if selector.observe(dt):
                    pass  # re-selected a precompiled degree; next step uses it

                history["loss"].append(float(metrics["loss"]))
                history["step_time"].append(dt)
                history["step"].append(step)
                step += 1
                if self.ckpt is not None:
                    self.ckpt.maybe_save(step, {"p": params, "o": opt_state})
            except SimulatedFailure:
                self.restarts += 1
                if self.restarts > max_restarts:
                    raise
                # node loss: restore the latest checkpoint and resume
                params, opt_state = self.init_state(key)
                step = 0
                if self.ckpt is not None:
                    restored = self.ckpt.restore_latest({"p": params, "o": opt_state})
                    if restored is not None:
                        step, tree = restored
                        params, opt_state = tree["p"], tree["o"]
        if self.ckpt is not None:
            self.ckpt.maybe_save(step, {"p": params, "o": opt_state}, force=True)
        self._final_params = params
        return history
