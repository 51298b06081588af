"""Training cells: the program's jitted train step, driven step by step.

Set-up makes the state from the seed on the device, compiles the step
once, and runs the first `check_steps` steps through the same call and
feed as the window; their losses, the first gradient (read back from the
optimizer's first moment) and the parameters' change over them are what
the reference is compared with.  Each step builds its batch on the host,
calls the step and fetches its metrics with `device_get`.  The window
keeps `AHEAD_S` seconds of steps (a fifth of the window at most, timed
by the last checked step) dispatched ahead of the one whose metrics it
waits for, so that the chip runs on while the host stands still.  Once
`--seconds` have passed it sends nothing more, waits for every step it
sent, and reads the clock after that wait: all of those steps count,
over all of that time.
"""

import collections
import math
import time

import jax
import jax.numpy as jnp

from . import device, faults, flops, weights
from . import traffic as gen
from .tracing import Window, settle, span

AHEAD_S = 6.0


def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _by_path(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def run(ctx) -> dict:
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.optim.optimizers import OptimizerConfig, build_optimizer
    from repro.runtime.parallel import ParallelContext, parallel_context
    from repro.runtime.sharding import state_shardings
    from repro.runtime.train import TrainConfig, make_train_step

    tr, m = ctx.traffic, ctx.config["model"]
    cfg = ModelConfig(**m)
    o = tr["optimizer"]
    tcfg = TrainConfig(optimizer=OptimizerConfig(**o),
                       z_loss_weight=tr["z_loss_weight"], remat=tr["remat"])
    step_fn, _ = make_train_step(cfg, tcfg)
    if ctx.fault:
        step_fn = faults.train(ctx.fault, step_fn)
    opt = build_optimizer(tcfg.optimizer)
    make_params = weights.maker(ctx.config)
    kd = weights.key_data(ctx.seed)
    n_check = ctx.limits["check_steps"]

    def make_state(kd):
        p = make_params(kd)
        return {"params": p, "opt": opt.init(p),
                "step": jnp.zeros((), jnp.int32)}

    def batch(s):
        b = gen.train_batch(tr, m["vocab_size"], ctx.seed, s)
        return {k: jnp.asarray(v) for k, v in b.items()}

    mesh = device.mesh(ctx.devices)
    with jax.set_mesh(mesh), parallel_context(ParallelContext()):
        abstract = jax.eval_shape(make_state, kd)
        weights.check_layout(abstract["params"], jax.eval_shape(
            build_model(cfg).init, jax.random.PRNGKey(0)))
        sh = state_shardings(mesh, abstract, o["name"])
        state = jax.jit(make_state, out_shardings=sh)(kd)
        step = jax.jit(step_fn, donate_argnums=0, in_shardings=(sh, None),
                       out_shardings=(sh, None)).lower(state,
                                                       batch(0)).compile()

        def send(state, s):
            with span("bench.batch"):
                b = batch(s)
            with span("bench.dispatch"):
                return step(state, b)

        def fetch(met):
            with span("bench.fetch"):
                return jax.device_get(met)

        losses, grad = [], None
        for s in range(n_check):
            t = time.perf_counter()
            state, met = send(state, s)
            met = fetch(met)
            step_s = time.perf_counter() - t
            losses.append(float(met["loss"]))
            if s == 0:
                grad = {k: v / (1.0 - o["b1"]) for k, v in _by_path(
                    jax.jit(_norms)(state["opt"]["mu"])).items()}
        # the first parameters made again as they were stored: a program
        # of their own, since XLA may drop a bf16 round trip inside one
        p0 = jax.jit(make_params)(kd)
        change = _by_path(jax.jit(lambda p, p0: _norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0)))(state["params"], p0))
        del p0
        jax.block_until_ready(state)
        settle()
        setup_s = time.perf_counter() - ctx.t_start

        ahead = max(1, math.ceil(min(AHEAD_S, ctx.seconds / 5) / step_s))
        window = Window(ctx.trace_dir, tr["trace_seconds"])
        sent, s = collections.deque(), n_check
        ends = [time.perf_counter()]
        window.start()
        while True:
            while len(sent) < ahead:
                state, met = send(state, s)
                sent.append(met)
                s += 1
            fetch(sent.popleft())
            ends.append(time.perf_counter())
            window.step_done()
            if ends[-1] - ends[0] >= ctx.seconds:
                break
        while sent:
            fetch(sent.popleft())
            ends.append(time.perf_counter())
            window.step_done()
        steps, elapsed = len(ends) - 1, ends[-1] - ends[0]
        window.stop()
        memory_peak = device.peak_bytes(ctx.devices)
        del state, step

    tokens = tr["batch"] * tr["seq_len"]
    return {
        "e2e": {"train_tokens_per_s": steps * tokens / elapsed,
                "setup_s": setup_s},
        "attempted": steps, "failed": 0, "memory_peak": memory_peak,
        "ends": ends,
        "counters": {"steps_traced": window.steps,
                     "window_s": window.window_s,
                     "flops_per_step": flops.train_step_flops(
                         ctx.config, tr["batch"], tr["seq_len"])},
        "program": {"loss": losses, "grad": grad, "change": change},
    }
