import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import jax  # noqa: E402

# compiled programs of the CPU tests stay out of the persistent cache
jax.config.update("jax_enable_compilation_cache", False)
