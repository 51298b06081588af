"""Host spans and the traced window.

Spans are `jax.profiler.TraceAnnotation`s, so they land in the profiler's
own trace on the device's clock; outside a trace they cost a few
microseconds.  `Window` traces the first `seconds` of a measured window
into `trace_dir`, inside a `bench.window` span that bounds it.
`settle` ends set-up.
"""

import gc
import time

import jax


def settle():
    """Collect Python's heap, which set-up fills with JAX's objects, and
    freeze what survives, so that no full collection of it (about 0.1 s)
    falls inside the window."""
    gc.collect()
    gc.freeze()


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class Window:
    def __init__(self, trace_dir, seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self.active, self.steps, self.window_s = False, 0, 0.0

    def start(self):
        if self.dir is None:
            return
        jax.profiler.start_trace(self.dir)
        self._span = span("bench.window")
        self._span.__enter__()
        self.t0, self.active = time.perf_counter(), True

    def step_done(self):
        """Count a step that ran inside the trace; end the trace once it
        has lasted its seconds."""
        if not self.active:
            return
        self.steps += 1
        if time.perf_counter() - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        if not self.active:
            return
        self.window_s = time.perf_counter() - self.t0
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
