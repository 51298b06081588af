"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 200 --batch 16 --seq 128 [--full] [--resume] \
        [--mesh host|one|pod|multipod] [--compress] [--microbatches 4] \
        [--profile-dir DIR --profile-steps 2:4]

Wires together everything the framework provides: mesh + sharding rules,
the ParallelContext (expert-parallel MoE, batch-pinned activations),
train_step under jit with state shardings, the step-indexed data
pipeline, async checkpointing, straggler tracking, and crash recovery
(restore-latest on failure, at most MAX_RESTARTS times; after that the
run fails).  The default is a reduced-width model; --full runs the
published widths.  `run(argv)` is the in-process entry point: it returns
the run's numbers, so a caller that holds the chip can drive it.

Each step runs inside `jax.profiler.StepTraceAnnotation("train")`, and the
checkpoint saves and restores inside the host spans `train.checkpoint`
and `train.restore`.  `--profile-dir DIR` traces steps A to B-1 of
`--profile-steps A:B` with `jax.profiler` into DIR and writes beside the
trace `program_ops.json`: the compiled step's module name and each
instruction's op_name, which names the model's scopes
(`repro.models.scopes`) that the trace's device ops belong to.
"""

import argparse
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import (AsyncCheckpointer, latest_steps,
                                           restore)
from repro.configs import ARCHS, reduced
from repro.data.pipeline import DataConfig, batch_for_model
from repro.launch.mesh import (compile_work, make_host_mesh,
                               make_one_device_mesh, make_production_mesh,
                               use_compile_cache)
from repro.models.scopes import program_ops
from repro.obs.metrics import get_logger
from repro.units import MEGA
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.compression import CompressionConfig
from repro.runtime.fault_tolerance import (StragglerMitigator,
                                           run_with_recovery)
from repro.runtime.parallel import ParallelContext, parallel_context
from repro.runtime.sharding import state_shardings
from repro.runtime.train import TrainConfig, make_train_step

log = get_logger("launch.train")

MAX_RESTARTS = 3          # failed steps restored before the run gives up

MESHES = {
    "host": make_host_mesh,
    "one": make_one_device_mesh,
    "pod": make_production_mesh,
    "multipod": lambda: make_production_mesh(multi_pod=True),
}


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="host", choices=list(MESHES))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--optimizer", default=None,
                    choices=[None, "adamw", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression on the DP all-reduce")
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "repro_train_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profile-dir", default=None,
                    help="trace the --profile-steps steps into this directory")
    ap.add_argument("--profile-steps", default="2:4", metavar="A:B",
                    help="steps A to B-1 are traced")
    return ap.parse_args(argv)


def span(name: str):
    """A host span in the profiler's trace, on the device's clock."""
    return jax.profiler.TraceAnnotation(name)


class StepProfile:
    """Traces the steps [first, last) once into `path`; inert without a
    path."""

    def __init__(self, path, steps: str):
        self.path, self.active, self.done = path, False, path is None
        self.first, self.last = (int(x) for x in steps.split(":"))

    def before(self, step: int):
        if not self.done and not self.active and (
                self.first <= step < self.last):
            jax.profiler.start_trace(self.path)
            self.active = True

    def after(self, step: int):
        if self.active and step + 1 >= self.last:
            self.stop()

    def stop(self):
        if self.active:
            jax.profiler.stop_trace()
            self.active, self.done = False, True


def run(argv=None) -> dict:
    """Train; returns the run's numbers (per-step ce/loss/seconds, compile
    seconds, restores, peak device bytes).  Raises once failing steps
    have been restored MAX_RESTARTS times."""
    args = parse_args(argv)
    use_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg, vocab_size=min(cfg.vocab_size, 8192))
    opt_name = args.optimizer or (
        "adafactor" if cfg.param_count() > 100e9 else "adamw")
    tcfg = TrainConfig(
        optimizer=OptimizerConfig(name=opt_name, lr=args.lr,
                                  warmup_steps=max(1, args.steps // 20),
                                  total_steps=args.steps),
        microbatches=args.microbatches,
        compression=CompressionConfig() if args.compress else None,
        remat=not args.reduced)
    step_fn, init_fn = make_train_step(cfg, tcfg)

    mesh = MESHES[args.mesh]()
    log.info(f"arch={cfg.name} reduced={args.reduced} "
             f"params~{cfg.param_count() / MEGA:.1f}M opt={opt_name} "
             f"mesh={dict(mesh.shape)}",
             params_m=cfg.param_count() / MEGA)

    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size)

    def batch_at(s):
        return {k: jnp.asarray(v)
                for k, v in batch_for_model(cfg, dcfg, s).items()}

    with jax.set_mesh(mesh), parallel_context(ParallelContext()):
        abstract = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
        st_sh = state_shardings(mesh, abstract, opt_name)
        jit_init = jax.jit(init_fn, out_shardings=st_sh)
        state = jit_init(jax.random.PRNGKey(0))

        ck = AsyncCheckpointer(args.ckpt_dir, keep=3)

        def save(state, step):
            with span("train.checkpoint"):
                ck.save_async(state, step)

        start = 0
        if args.resume and latest_steps(args.ckpt_dir):
            with span("train.restore"):
                state = restore(args.ckpt_dir, state, shardings=st_sh)
            start = int(jax.device_get(state["step"]))
            log.info(f"resumed at step {start}", step=start)

        t0 = time.perf_counter()
        with compile_work() as work:
            jit_step = jax.jit(step_fn, donate_argnums=0,
                               in_shardings=(st_sh, None),
                               out_shardings=(st_sh, None)
                               ).lower(state, batch_at(start)).compile()
        compile_s = time.perf_counter() - t0
        log.info(f"step program compiled in {compile_s:.1f}s "
                 f"({work['cache_misses']} compile cache misses)",
                 compile_s=compile_s, cache_misses=work["cache_misses"])
        profile = StepProfile(args.profile_dir, args.profile_steps)
        if args.profile_dir:
            os.makedirs(args.profile_dir, exist_ok=True)
            with open(os.path.join(args.profile_dir,
                                   "program_ops.json"), "w") as f:
                json.dump(program_ops(jit_step.as_text()), f)

        straggler = StragglerMitigator()
        step_s = []                 # seconds of each step in metrics_log

        def one_step(state, batch):
            s = start + len(step_s)
            profile.before(s)
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("train", step_num=s):
                state, metrics = jit_step(state, batch)
                metrics = jax.device_get(metrics)   # waits for the device
            dt = time.perf_counter() - t0
            profile.after(s)
            step_s.append(dt)
            straggler.record(0, dt)
            if s % args.log_every == 0 or s == args.steps - 1:
                log.info(f"step {s:5d} ce={float(metrics['ce']):.4f} "
                         f"loss={float(metrics['loss']):.4f} "
                         f"tok/s={args.batch * args.seq / dt:,.0f}",
                         step=s, ce=float(metrics["ce"]),
                         loss=float(metrics["loss"]))
            if straggler.stragglers():
                log.warning(
                    f"stragglers detected: {straggler.stragglers()}",
                    n_stragglers=len(straggler.stragglers()))
            return state, metrics

        def restore_latest():
            ck.wait()
            if latest_steps(args.ckpt_dir):
                with span("train.restore"):
                    restored = restore(args.ckpt_dir, abstract,
                                       shardings=st_sh)
                s = int(jax.device_get(restored["step"]))
            else:
                restored, s = jit_init(jax.random.PRNGKey(0)), 0
            log.error(f"step failed; restarting from step {s}", step=s)
            del step_s[max(0, s - start):]      # those steps are replayed
            return restored, s

        t_run = time.perf_counter()
        try:
            state, events, metrics_log = run_with_recovery(
                one_step, state, args.steps, batch_at, save,
                restore_latest, checkpoint_every=args.ckpt_every,
                max_restarts=MAX_RESTARTS, start=start)
        finally:
            profile.stop()
        save(state, args.steps)
        ck.wait()
        wall_s = time.perf_counter() - t_run
        log.info(f"finished {args.steps - start} steps in {wall_s:.1f}s; "
                 f"checkpoints: {latest_steps(args.ckpt_dir)}",
                 steps_run=args.steps - start, wall_s=wall_s)

    stats = mesh.devices.flat[0].memory_stats() or {}
    return {
        "arch": cfg.name, "reduced": args.reduced,
        "params": cfg.param_count(), "vocab_size": cfg.vocab_size,
        "mesh": dict(mesh.shape),
        "tokens_per_step": args.batch * args.seq,
        "compile_s": compile_s,
        "step_s": step_s,
        "ce": [float(m["ce"]) for m in metrics_log],
        "loss": [float(m["loss"]) for m in metrics_log],
        "restores": len(events),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def main():
    run()


if __name__ == "__main__":
    main()
