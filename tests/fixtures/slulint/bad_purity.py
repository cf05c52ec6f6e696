"""Seeded violation: host-only calls inside traced code."""
import time

import jax
import numpy as np

from superlu_dist_tpu import flags


@jax.jit
def stamped_step(x):
    t0 = time.time()            # trace-time constant, not a clock
    noise = np.random.rand()    # baked-in "random" draw
    knob = flags.env_int("SLU_COOP_MB", 256)  # frozen knob
    return x * noise + t0 + knob


def looped(x):
    def body(i, acc):
        print("iter", i)        # fires once per signature, at trace
        return acc + i
    return jax.lax.fori_loop(0, 8, body, x)
