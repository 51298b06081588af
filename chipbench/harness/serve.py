"""Serving cells: the program's decode step under a closed loop.

Each of `clients` clients owns one of `slots` cache rows and sends its
next request as soon as the last one completes.  A request is taken into
its row at position 0 and fed one prompt token per decode step (the
program has no prefill into the cache); the step that is fed the last
prompt token yields the first output token, and every later step feeds
back the token it yielded, until the answer has its length.  The loop is
the benchmark's own copy of the program's per-slot refill loop, with the
host's per-slot state held in arrays.

Set-up compiles the step and runs `warmup_steps` steps of the loop, so
that the window opens with rows at different points of their requests.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import device, faults, flops, weights
from . import traffic as gen
from .tracing import Window, settle, span


class Loop:
    """Host state of every slot, and what the window records."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.req = gen.Requests(traffic, vocab, seed)
        n = traffic["slots"]
        if n != traffic["clients"]:
            raise ValueError("the closed loop gives each client one slot")
        self.pmax = traffic["prompt"]["max"]
        self.prompt = np.zeros((n, self.pmax), np.int32)
        self.gen = np.zeros((n, traffic["answer"]["max"]), np.int32)
        self.plen = np.zeros(n, np.int64)      # prompt length
        self.alen = np.zeros(n, np.int64)      # answer length
        self.k = np.zeros(n, np.int64)         # the client's request count
        self.pos = np.zeros(n, np.int32)       # position fed next
        self.ngen = np.zeros(n, np.int64)      # tokens served so far
        self.last = np.zeros(n, np.int32)
        self.admit_t, self.first_t = np.zeros(n), np.zeros(n)
        self.rows = np.arange(n)
        self.recording = False
        self.ttft, self.tpot, self.done, self.tokens = [], [], [], 0
        self.live_traced = []
        for s in range(n):
            self.admit(s, 0.0)

    def start(self, t: float):
        """The first requests are taken into their slots at t."""
        self.admit_t[:] = t

    def admit(self, s: int, t: float):
        prompt, answer = self.req.get(s, int(self.k[s]))
        self.prompt[s, :len(prompt)] = prompt
        self.plen[s], self.alen[s] = len(prompt), answer
        self.pos[s], self.ngen[s], self.admit_t[s] = 0, 0, t

    def feed(self):
        at = np.minimum(self.pos, self.pmax - 1)
        tok = np.where(self.pos < self.plen, self.prompt[self.rows, at],
                       self.last)
        return tok[:, None].astype(np.int32), self.pos.copy()

    def advance(self, nxt: np.ndarray, t: float):
        out = self.pos >= self.plen - 1          # this step yielded a token
        first = out & (self.ngen == 0)
        self.gen[self.rows[out], self.ngen[out]] = nxt[out]
        self.first_t[first] = t
        self.ngen += out
        self.last = nxt
        self.pos += 1
        if self.recording:
            self.tokens += int(out.sum())
            self.ttft.extend(t - self.admit_t[first])
        for s in np.flatnonzero(self.ngen >= self.alen):
            if self.recording:
                n = int(self.alen[s])
                self.tpot.append((t - self.first_t[s]) / (n - 1))
                self.done.append((self.prompt[s, :self.plen[s]].copy(),
                                  self.gen[s, :n].copy()))
            self.k[s] += 1
            self.admit(s, t)


def run(ctx) -> dict:
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.runtime.parallel import ParallelContext, parallel_context
    from repro.runtime.serve import ServeConfig, make_serve_fns

    tr, m = ctx.traffic, ctx.config["model"]
    cfg = ModelConfig(**m)
    L, slots = tr["cache_len"], tr["slots"]
    if tr["prompt"]["max"] + tr["answer"]["max"] - 1 > L:
        raise ValueError("the longest request does not fit the cache")
    _, decode_step, init_cache = make_serve_fns(cfg, ServeConfig(max_len=L))
    if ctx.fault:
        decode_step = faults.serve(ctx.fault, decode_step, m["vocab_size"])
    make_params = weights.maker(ctx.config)
    kd = weights.key_data(ctx.seed)
    loop = Loop(tr, m["vocab_size"], ctx.seed)

    mesh = device.mesh(ctx.devices)
    with jax.set_mesh(mesh), parallel_context(ParallelContext()):
        weights.check_layout(jax.eval_shape(make_params, kd), jax.eval_shape(
            build_model(cfg).init, jax.random.PRNGKey(0)))
        params = jax.jit(make_params)(kd)
        cache = jax.jit(lambda: init_cache(slots, L))()
        tok, pos = loop.feed()
        step = jax.jit(decode_step, donate_argnums=1).lower(
            params, cache, tok, pos).compile()

        def one():
            with span("bench.feed"):
                tok, pos = loop.feed()
            with span("bench.dispatch"):
                nxt, _, c = step(params, cache, tok, pos)
            with span("bench.fetch"):
                nxt = np.asarray(nxt)[:, 0]
            t = time.perf_counter()
            with span("bench.refill"):
                loop.advance(nxt, t)
            return c, pos

        loop.start(time.perf_counter())
        for _ in range(tr["warmup_steps"]):
            cache, _ = one()
        jax.block_until_ready(cache)
        settle()
        setup_s = time.perf_counter() - ctx.t_start

        window = Window(ctx.trace_dir, tr["trace_seconds"])
        loop.recording = True
        ends = [time.perf_counter()]
        window.start()
        while True:
            traced = window.active
            cache, pos = one()
            ends.append(time.perf_counter())
            if traced:
                loop.live_traced.append(int(pos.sum()) + slots)
            window.step_done()
            if ends[-1] - ends[0] >= ctx.seconds:
                break
        elapsed = ends[-1] - ends[0]
        window.stop()
        memory_peak = device.peak_bytes(ctx.devices)
        del params, cache, step

    return {
        "e2e": {"serve_output_tokens_per_s": loop.tokens / elapsed,
                "serve_ttft_p95_ms": 1e3 * float(np.percentile(loop.ttft, 95)),
                "serve_tpot_p95_ms": 1e3 * float(np.percentile(loop.tpot, 95)),
                "setup_s": setup_s},
        "attempted": len(loop.done), "failed": 0,
        "memory_peak": memory_peak, "ends": ends,
        "counters": {"steps_traced": window.steps,
                     "window_s": window.window_s,
                     "decode": [flops.dense_decode_step(m, slots, n)
                                for n in loop.live_traced]},
        "program": {"done": loop.done},
    }
