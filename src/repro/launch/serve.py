"""Serving launcher: batched decode with continuous batching, on the
host's mesh (every device on the `model` axis).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 16 --slots 4 --max-new 16 [--full]

Each of --slots cache rows serves one request at a time and is refilled
from the queue as soon as its request finishes.  Every row decodes at its
own position, so a refilled row starts at position 0 and never sees the
cache entries its previous request left behind.  The default is a
reduced-width model; --full runs the published widths.  `run(argv)` is
the in-process entry point and returns every request's tokens and the
logits the server produced for them; `reference_logits` recomputes those
logits with one teacher-forced full-sequence forward.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.launch.mesh import make_host_mesh, use_compile_cache
from repro.models import build_model
from repro.obs.metrics import get_logger
from repro.runtime.parallel import ParallelContext, parallel_context
from repro.runtime.serve import ServeConfig, make_serve_fns

log = get_logger("launch.serve")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCHS))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    return ap.parse_args(argv)


def run(argv=None) -> dict:
    """Serve the request queue.  Returns the config, the params, and per
    request its prompt, generated tokens and the (len(prompt) +
    len(tokens) - 1, V) logits of every decode step it was fed in."""
    args = parse_args(argv)
    use_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg, vocab_size=min(cfg.vocab_size, 4096))
    mesh = make_host_mesh()
    scfg = ServeConfig(max_len=args.max_len)

    rng = np.random.default_rng(0)
    queue = [[int(t) for t in rng.integers(1, cfg.vocab_size,
                                           size=int(rng.integers(2, 6)))]
             for _ in range(args.requests)]
    longest = max(len(p) for p in queue) + args.max_new
    if longest > args.max_len:
        raise ValueError(f"a request needs {longest} cache positions; "
                         f"--max-len is {args.max_len}")

    with jax.set_mesh(mesh), parallel_context(ParallelContext()):
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        _, decode_step, init_cache = make_serve_fns(cfg, scfg)
        cache = init_cache(args.slots, args.max_len)
        feed = np.zeros((args.slots, 1), np.int32)
        pos = np.zeros((args.slots,), np.int32)
        t0 = time.perf_counter()
        dec = jax.jit(decode_step, donate_argnums=1).lower(
            params, cache, feed, pos).compile()
        compile_s = time.perf_counter() - t0

        # per slot: request id, prompt tokens not yet fed, position
        active = [None] * args.slots
        results = []
        t0 = time.perf_counter()
        steps = 0
        while queue or any(active):
            for s in range(args.slots):
                if active[s] is None and queue:
                    prompt = queue.pop(0)
                    results.append({"prompt": prompt, "tokens": [],
                                    "logits": []})
                    active[s] = [len(results) - 1, list(prompt), 0]
            for s, a in enumerate(active):
                if a is not None:
                    rid, pending, p = a
                    feed[s, 0] = (pending.pop(0) if pending
                                  else results[rid]["tokens"][-1])
                    pos[s] = p
            nxt, logits, cache = dec(params, cache, feed, pos)
            nxt, logits = np.asarray(nxt), np.asarray(logits)
            steps += 1
            for s, a in enumerate(active):
                if a is None:
                    continue
                rid, pending, _ = a
                a[2] += 1
                r = results[rid]
                r["logits"].append(logits[s, 0])
                if not pending:
                    r["tokens"].append(int(nxt[s, 0]))
                    if len(r["tokens"]) >= args.max_new:
                        active[s] = None
        dt = time.perf_counter() - t0
    for r in results:
        r["logits"] = np.stack(r["logits"])
    log.info(f"served {len(results)}/{args.requests} requests, "
             f"{steps} decode steps x {args.slots} slots in {dt:.1f}s "
             f"(decode compiled in {compile_s:.1f}s)",
             served=len(results), steps=steps, wall_s=dt,
             compile_s=compile_s)
    return {"cfg": cfg, "params": params, "requests": results,
            "decode_steps": steps, "compile_s": compile_s, "wall_s": dt}


def reference_logits(cfg, params, requests) -> list:
    """Teacher-forced logits for each request: one full-sequence forward
    over its prompt and generated tokens (the last generated token is
    never fed back), the same rows `run` records from the decode path."""
    seqs = [r["prompt"] + r["tokens"][:-1] for r in requests]
    tokens = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s       # causal: the padding after s is unseen
    model = build_model(cfg, remat=False)
    logits, _ = jax.jit(model.apply)(params, {"tokens": jnp.asarray(tokens)})
    logits = np.asarray(logits)
    return [logits[i, :len(s)] for i, s in enumerate(seqs)]


def main():
    run()


if __name__ == "__main__":
    main()
