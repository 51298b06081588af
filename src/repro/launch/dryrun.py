"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the abstract train/serve state (jax.eval_shape — no allocation),
  2. assigns shardings from runtime/sharding.py rules,
  3. jit(...).lower(**input_specs).compile() on the production mesh
     (16x16 single-pod / 2x16x16 multi-pod of host placeholder devices),
  4. records memory_analysis() + cost_analysis() + parsed collective bytes,
  5. lowers the single-unit programs and extrapolates the roofline
     (DESIGN.md S7),
and writes one JSON per cell under experiments/dryrun/.

Usage (main() gives the CPU backend 512 placeholder devices):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
      --arch smollm-360m --shape train_4k --mesh pod
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun --all \
      --mesh both
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cells, get_arch
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as RL
from repro.launch.unit_programs import (decode_unit_programs,
                                        train_unit_programs)
from repro.models import build_model
from repro.obs.metrics import get_logger
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.sharding import (cache_shardings, logical_batch_shardings,
                                    params_shardings, state_shardings)
from repro.runtime.train import TrainConfig, make_train_step
from repro.runtime.parallel import ParallelContext, parallel_context
import contextlib

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

log = get_logger("launch.dryrun")


def optimizer_for(cfg: ModelConfig) -> OptimizerConfig:
    """Adafactor for >=100B params (kimi/mixtral would not fit AdamW state
    on the assigned meshes; DESIGN.md S6), AdamW otherwise."""
    big = cfg.param_count() > 100e9
    return OptimizerConfig(name="adafactor" if big else "adamw")


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.mode in ("train", "prefill"):
        batch = {}
        if cfg.is_encdec:
            batch["src_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       jnp.bfloat16)
            batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        elif cfg.frontend == "embed":
            batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   jnp.bfloat16)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        if shape.mode == "train":
            batch["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        return batch
    # decode: one new token against a seq_len cache
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(B, S, src_len=1024))
    return {"token": jax.ShapeDtypeStruct((B, 1), i32),
            "cache": cache,
            "pos": jax.ShapeDtypeStruct((), i32)}


def _mem_dict(ma) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def lower_train_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     attention_impl: str = "auto",
                     sharding_overrides=None, train_overrides=None):
    tcfg = TrainConfig(optimizer=optimizer_for(cfg),
                       attention_impl=attention_impl,
                       **(train_overrides or {}))
    step_fn, init_fn = make_train_step(cfg, tcfg)
    abstract_state = jax.eval_shape(
        lambda: init_fn(jax.random.PRNGKey(0)))
    st_sh = state_shardings(mesh, abstract_state, tcfg.optimizer.name)
    if sharding_overrides:
        st_sh = sharding_overrides(mesh, abstract_state, st_sh)
    batch = input_specs(cfg, shape)
    b_sh = logical_batch_shardings(mesh, batch)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step_fn, in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, NamedSharding(mesh, P())),
        ).lower(abstract_state, batch)
        compiled = lowered.compile()
    return lowered, compiled, abstract_state


def lower_prefill_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       attention_impl: str = "auto"):
    """Serving prefill: full-sequence forward, last-position logits only."""
    model = build_model(cfg, impl=attention_impl, remat=True)
    batch = input_specs(cfg, shape)
    abstract_params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = params_shardings(mesh, abstract_params)
    b_sh = logical_batch_shardings(mesh, batch)

    def prefill(params, batch):
        logits, _ = model.apply(params, batch)
        return logits[:, -1]

    with jax.set_mesh(mesh):
        lowered = jax.jit(prefill, in_shardings=(p_sh, b_sh)).lower(
            abstract_params, batch)
        compiled = lowered.compile()
    return lowered, compiled, abstract_params


def lower_decode_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      attention_impl: str = "auto"):
    model = build_model(cfg, impl=attention_impl, remat=False)
    specs = input_specs(cfg, shape)
    abstract_params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = params_shardings(mesh, abstract_params)
    c_sh = cache_shardings(mesh, specs["cache"])
    t_sh = logical_batch_shardings(mesh, {"t": specs["token"]})["t"]
    rep = NamedSharding(mesh, P())

    def serve_step(params, cache, token, pos):
        return model.decode(params, cache, token, pos)

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            serve_step,
            in_shardings=(p_sh, c_sh, t_sh, rep),
            out_shardings=(t_sh, c_sh),
        ).lower(abstract_params, specs["cache"], specs["token"],
                specs["pos"])
        compiled = lowered.compile()
    return lowered, compiled, abstract_params, specs


def lower_unit(fn, abstract_args, mesh):
    """Lower a unit program with rule-derived shardings for each arg."""
    from repro.runtime.sharding import batch_spec, cache_spec, param_spec

    def shard_tree(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for kp, x in flat:
            name = "/".join(str(getattr(k, "key", k)) for k in kp)
            if x.dtype == jnp.bfloat16 and x.ndim == 3 and not name:
                spec = batch_spec(mesh, x.shape)
            elif "k" == name.split("/")[-1] or "v" == name.split("/")[-1] \
                    or "conv" in name or "state" in name:
                spec = cache_spec(mesh, x.shape)
            else:
                spec = param_spec(mesh, name, x.shape)
            out.append(NamedSharding(mesh, spec))
        return jax.tree_util.tree_unflatten(treedef, out)

    shardings = tuple(
        shard_tree(a) if isinstance(a, dict)
        else NamedSharding(mesh, batch_spec(mesh, a.shape))
        if getattr(a, "ndim", 0) >= 2
        else NamedSharding(mesh, P())
        for a in abstract_args)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings).lower(*abstract_args)
        return lowered.compile()


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             attention_impl: str = "auto", with_roofline: bool = True,
             out_dir: str = OUT_DIR, train_overrides=None,
             tag: str = "", moe_parallel: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.devices.size
    t0 = time.time()
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "chips": int(n_chips), "mode": shape.mode,
              "moe_parallel": moe_parallel}
    pctx = parallel_context(ParallelContext()) if moe_parallel \
        else contextlib.nullcontext()
    try:
      with pctx:
          if shape.mode == "decode":
              lowered, compiled, abs_params, specs = lower_decode_cell(
                  cfg, shape, mesh, attention_impl)
          elif shape.mode == "prefill":
              lowered, compiled, _ = lower_prefill_cell(
                  cfg, shape, mesh, attention_impl)
          else:
              lowered, compiled, abstract_state = lower_train_cell(
                  cfg, shape, mesh, attention_impl,
                  train_overrides=train_overrides)
          result["memory"] = _mem_dict(compiled.memory_analysis())
          ca = compiled.cost_analysis()
          result["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                     if isinstance(v, (int, float))}

          if with_roofline:
              units = []
              if shape.mode == "decode":
                  progs = decode_unit_programs(cfg, abs_params,
                                               specs["cache"],
                                               shape.global_batch)
              elif shape.mode == "train":
                  progs = train_unit_programs(cfg, abstract_state,
                                              shape.global_batch,
                                              shape.seq_len, attention_impl)
              else:  # prefill: forward-only units
                  model = build_model(cfg, impl=attention_impl)
                  abs_params = jax.eval_shape(
                      lambda: model.init(jax.random.PRNGKey(0)))
                  progs = train_unit_programs(
                      cfg, {"params": abs_params}, shape.global_batch,
                      shape.seq_len, attention_impl, grad=False)
              rl = RL.extract(compiled)
              per_unit = []
              for name, fn, args, k in progs:
                  uc = lower_unit(fn, args, mesh)
                  u = RL.extract(uc)
                  per_unit.append({"name": name, "k": k, **u.as_dict()})
                  rl = RL.Roofline(
                      rl.flops + k * u.flops,
                      rl.hbm_bytes + k * u.hbm_bytes,
                      rl.coll_link_bytes + k * u.coll_link_bytes,
                      {**rl.coll_per_op,
                       **{o: rl.coll_per_op.get(o, 0.0) + k * v
                          for o, v in u.coll_per_op.items()}})
              tokens = shape.global_batch * (shape.seq_len
                                             if shape.mode != "decode" else 1)
              mf = RL.model_flops(cfg.param_count(), cfg.active_param_count(),
                                  tokens, shape.mode)
              result["roofline"] = rl.as_dict()
              result["roofline"]["units"] = per_unit
              result["roofline"]["model_flops_global"] = mf
              result["roofline"]["model_flops_per_chip"] = mf / n_chips
              result["roofline"]["useful_ratio"] = (
                  mf / n_chips / rl.flops if rl.flops else 0.0)
          result["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
    result["seconds"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = os.path.join(out_dir,
                      f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    # before the first device query: the CPU backend reads it once
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attention-impl", default="auto")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="resume: skip cells whose JSON already exists ok")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    targets = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        for s in cells(a):
            if args.shape and s.name != args.shape:
                continue
            targets.append((a, s.name))
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for a, s in targets:
        for mk in meshes:
            fn = os.path.join(args.out, f"{a}__{s}__{mk}.json")
            if args.skip_existing and os.path.exists(fn):
                try:
                    if json.load(open(fn)).get("status") == "ok":
                        log.info(f"{a:22s} {s:12s} {mk:8s} skip (exists)")
                        continue
                except Exception:
                    pass
            r = run_cell(a, s, mk, args.attention_impl,
                         not args.no_roofline, args.out)
            dom = r.get("roofline", {}).get("dominant", "-")
            mem = r.get("memory", {}).get("argument_size_in_bytes", 0)
            log.info(f"{a:22s} {s:12s} {mk:8s} {r['status']:5s} "
                     f"args/dev={mem/2**30:7.2f}GiB dominant={dom:10s} "
                     f"{r['seconds']:6.1f}s",
                     seconds=r["seconds"])
            if r["status"] != "ok":
                failures += 1
                log.error(r["error"])
    log.info(f"done: {len(targets) * len(meshes) - failures} ok, "
             f"{failures} failed", failures=failures)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
