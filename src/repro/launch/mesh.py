"""Mesh construction and the persistent compile cache.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state.
"""

from __future__ import annotations

import contextlib
import os

import jax

# <checkout>/.jax_cache: a fixed path, since the path is part of the key
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", "..", ".jax_cache"))


def use_compile_cache() -> None:
    """Keep compiled programs across processes; call before the first
    compile.  Where JAX_COMPILATION_CACHE_DIR is set, jax already reads it
    and nothing is changed; otherwise the cache goes to CACHE_DIR."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


# tracing, lowering, and compiling or fetching from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


@contextlib.contextmanager
def compile_work():
    """Tally the compile work JAX reports while inside: `compile_s`, the
    seconds of COMPILE_EVENTS (the backend's share includes fetches from
    the persistent cache), `cache_retrieval_s`, those fetches alone, and
    `cache_misses`, programs the cache did not hold."""
    got = {"compile_s": 0.0, "cache_retrieval_s": 0.0, "cache_misses": 0}

    def on_duration(event, secs, **_):
        if event in COMPILE_EVENTS:
            got["compile_s"] += secs
        elif event == CACHE_RETRIEVAL:
            got["cache_retrieval_s"] += secs

    def on_event(event, **_):
        if event == CACHE_MISS:
            got["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def make_auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis Auto (GSPMD decides the collectives)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Every device of this host on the model axis: (data=1, model=n)."""
    n = len(jax.devices())
    return make_auto_mesh((1, n), ("data", "model"))


def make_one_device_mesh():
    """(data=1, model=1) on the first device: the unsharded reference."""
    return make_auto_mesh((1, 1), ("data", "model"),
                          devices=jax.devices()[:1])
