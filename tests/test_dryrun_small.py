"""Dry-run machinery smoke: a reduced arch lowers+compiles on a tiny mesh
within this process (the full 512-device sweep runs via the module CLI;
its 66-cell results are recorded in experiments/)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.runtime.sharding import (logical_batch_shardings,
                                    state_shardings)
from repro.runtime.train import TrainConfig, make_train_step
from repro.optim.optimizers import OptimizerConfig
from repro.launch.mesh import make_auto_mesh


def test_lower_compile_reduced_arch():
    cfg = reduced(ARCHS["chatglm3-6b"])
    tcfg = TrainConfig(optimizer=OptimizerConfig(), remat=True)
    step_fn, init_fn = make_train_step(cfg, tcfg)
    abstract_state = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
    n = len(jax.devices())
    mesh = make_auto_mesh((1, n), ("data", "model"))
    st_sh = state_shardings(mesh, abstract_state, "adamw")
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    b_sh = logical_batch_shardings(mesh, batch)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, NamedSharding(mesh, P()))
                           ).lower(abstract_state, batch).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 0
    assert compiled.cost_analysis().get("flops", 0) > 0


def test_dryrun_results_complete():
    """The recorded 66-cell sweep must be complete and all-ok."""
    import glob
    import json
    import os
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "dryrun")
    files = glob.glob(os.path.join(d, "*.json"))
    files = [f for f in files if "__h_" not in f]   # exclude hillclimb tags
    if len(files) < 66:
        pytest.skip("full sweep artifacts not present")
    cells = [json.load(open(f)) for f in files]
    ok = [c for c in cells if c.get("status") == "ok"]
    assert len(ok) >= 66, [c["arch"] + c["shape"] for c in cells
                           if c.get("status") != "ok"]
