"""Every architecture's train step, at reduced widths, lowers and compiles
under the state and batch shardings on a (data=1, model=n) mesh of this
host's devices."""

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.launch.mesh import make_auto_mesh
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.sharding import (logical_batch_shardings,
                                    state_shardings)
from repro.runtime.train import TrainConfig, make_train_step

from arch_batch import arch_batch


@pytest.mark.parametrize("arch", list(ARCHS))
def test_lower_compile_reduced_arch(arch):
    cfg = reduced(ARCHS[arch])
    tcfg = TrainConfig(optimizer=OptimizerConfig(), remat=True)
    step_fn, init_fn = make_train_step(cfg, tcfg)
    abstract_state = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
    n = len(jax.devices())
    mesh = make_auto_mesh((1, n), ("data", "model"))
    st_sh = state_shardings(mesh, abstract_state, "adamw")
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in arch_batch(cfg).items()}
    b_sh = logical_batch_shardings(mesh, batch)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, NamedSharding(mesh, P()))
                           ).lower(abstract_state, batch).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 0
    assert compiled.cost_analysis().get("flops", 0) > 0
