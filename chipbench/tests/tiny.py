"""A benchmark tree with tiny cells added as new files and entries alone,
for tests on the CPU."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_DENSE = {"name": "tiny-dense", "family": "dense", "reference": "dense",
              "init": {"out_gain": 4.0},
              "model": {"name": "tiny-dense", "family": "dense",
                        "n_layers": 2, "d_model": 64, "n_heads": 4,
                        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                        "vocab_size": 256, "rope_theta": 10000.0,
                        "norm_eps": 1e-05, "tie_embeddings": True,
                        "activation": "silu"}}
TINY_SSM = {"name": "tiny-ssm", "family": "ssm", "reference": "ssm",
            "model": {"name": "tiny-ssm", "family": "ssm", "n_layers": 2,
                      "d_model": 64, "n_heads": 1, "n_kv_heads": 1,
                      "head_dim": 16, "d_ff": 0, "vocab_size": 256,
                      "ssm_state": 16, "d_conv": 4, "expand": 2,
                      "ssm_head_dim": 16, "ssm_chunk": 16,
                      "norm_eps": 1e-05, "tie_embeddings": True}}
TRAIN = {"kind": "train", "batch": 4, "seq_len": 32,
         "optimizer": {"name": "adamw", "lr": 0.0003, "warmup_steps": 1,
                       "total_steps": 10000, "weight_decay": 0.01,
                       "b1": 0.9, "b2": 0.999, "eps": 1e-08,
                       "clip_norm": 1.0},
         "z_loss_weight": 0.0001, "remat": True, "trace_seconds": 0.5}
SERVE = {"kind": "serve", "loop": "closed", "clients": 4, "slots": 4,
         "cache_len": 48, "requests_per_client": 8,
         "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
         "answer": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
         "warmup_steps": 8, "trace_seconds": 0.5}
# set as the cells' limits are, from readings on the CPU over 5 seeds:
# sound loss_gap up to 0.0019, grad_gap 0.0185, change_gap 0.0128; the
# control's least loss_gap 0.0103 (its other numbers overlap the sound)
TRAIN_LIMITS = {"check_steps": 3, "reference_rows": 2,
                "limits": {"loss_gap": 0.005, "grad_gap": 0.05,
                           "change_gap": 0.05}}
SERVE_LIMITS = {"sample_requests": 4, "limits": {"logit_gap": 0.1}}

SERVE_END_TO_END = [
    ("serve_output_tokens_per_s", "tokens/s", "higher"),
    ("serve_ttft_p95_ms", "ms", "lower"),
    ("serve_tpot_p95_ms", "ms", "lower"),
]
SERVE_PER_LAYER = [
    ("step_device_ms.serve", "ms", "lower", "step programs",
     "serve_tpot_p95_ms"),
    ("mfu.serve", "%", "higher", "device", "serve_output_tokens_per_s"),
    ("idle_share.serve", "%", "lower", "device", "serve_output_tokens_per_s"),
]

CELLS = {
    "tiny-dense.train.tiny": ("tiny-dense", "train.tiny", TRAIN_LIMITS),
    "tiny-ssm.train.tiny": ("tiny-ssm", "train.tiny", TRAIN_LIMITS),
    "tiny-dense.serve.tiny": ("tiny-dense", "serve.tiny", SERVE_LIMITS),
}


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def _add_config(spec: dict, bench: str, cfg: dict) -> None:
    _dump(cfg, bench, "configs", cfg["name"] + ".json")
    spec["configs"].append({
        "name": cfg["name"], "source": "https://example.org/tiny",
        "file": f"chipbench/configs/{cfg['name']}.json",
        "reduced": [], "why": "tiny"})


def _add_cell(spec: dict, bench: str, name: str, cfg: str, traffic: str,
              limits: dict) -> None:
    _dump(limits, bench, "limits", name + ".json")
    spec["workloads"].append({"name": name, "config": cfg,
                              "traffic": traffic, "chips": 1,
                              "why": "tiny"})
    if ".train." in name:
        for m in spec["end_to_end"] + spec["per_layer"]:
            if any(".train." in w for w in m.get("workloads", [])):
                m["workloads"].append(name)


# a family added as one new module: the dense reference under another
# name, with a configuration of its own that changes one width
NEW_FAMILY = "decoder"
TINY_NEW = dict(TINY_DENSE, name="tiny-new", reference=NEW_FAMILY,
                model=dict(TINY_DENSE["model"], name="tiny-new", d_ff=96))


def add_family(root: str) -> str:
    """Add to the tree at root, as new files and entries alone, the module
    reference/<NEW_FAMILY>.py, the configuration TINY_NEW and its train
    cell; returns the cell's name."""
    bench = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(BENCH, "reference", "dense.py"),
                os.path.join(bench, "reference", NEW_FAMILY + ".py"))
    spec = _load(root, "BENCHMARK.json")
    _add_config(spec, bench, TINY_NEW)
    name = TINY_NEW["name"] + ".train.tiny"
    _add_cell(spec, bench, name, TINY_NEW["name"], "train.tiny",
              TRAIN_LIMITS)
    _dump(spec, root, "BENCHMARK.json")
    return name


def set_chips(root: str, workload: str, chips: int) -> None:
    """The cell `workload` of the tree at root asks for `chips` chips."""
    spec = _load(root, "BENCHMARK.json")
    next(w for w in spec["workloads"] if w["name"] == workload)[
        "chips"] = chips
    _dump(spec, root, "BENCHMARK.json")


def make(tmp: str) -> str:
    """A copy of the benchmark at tmp with the tiny cells added; returns
    the root to run from."""
    shutil.copytree(BENCH, os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = os.path.join(tmp, "chipbench")
    spec = _load(ROOT, "BENCHMARK.json")
    for cfg in (TINY_DENSE, TINY_SSM):
        _add_config(spec, bench, cfg)
    _dump(TRAIN, bench, "traffic", "train.tiny.json")
    _dump(SERVE, bench, "traffic", "serve.tiny.json")
    for name, (cfg, traffic, limits) in CELLS.items():
        _add_cell(spec, bench, name, cfg, traffic, limits)
    # no serving cell is committed yet: its metrics come with it, as
    # entries of their own
    serve = ["tiny-dense.serve.tiny"]
    spec["end_to_end"] += [
        {"name": n, "unit": u, "better": b, "bound": 0.25,
         "source": "host_clock", "workloads": serve}
        for n, u, b in SERVE_END_TO_END]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": "device_trace",
         "layer": layer, "moves": moves, "workloads": serve}
        for n, u, b, layer, moves in SERVE_PER_LAYER]
    _dump(spec, tmp, "BENCHMARK.json")
    return tmp
