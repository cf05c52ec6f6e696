"""Generator kind `zstep`: kind `step`'s closed loop on a COMPLEX
configuration.  The timed loop is kinds/step.py's own (`_step`,
`setup`, `reseed`, `warm`, `window`, `check`: one more instance of
that module, loaded here, not a copy of its code); what differs is the
yardstick's side of it, which step.py takes from `reference` by name:
this instance takes `value_sets`, `systems` and `Checker` from
`reference_z` (complex value sets and right-hand sides from the seed;
the comparison in complex128).  Parameters (traffic file): those of
kind `step`.

The configuration's deployment is one chip, and the cell measures the
complex factorization and sweeps ON it.  A program that would place
them on the host CPU backend instead (`utils/platform.complex_needs_cpu`
true of the factor dtype: the tree before PR 32, whose gate moves
every complex program off a TPU) cannot run this configuration: the
run is refused before any set-up, with a code other than 0 and no
result line, as where there is no TPU.  Its steps would be timed on
the host's cores, and its trace would hold no device operation."""

import harness
import reference_z

_step = harness.load_module("kind_step_for_zstep", "kinds", "step.py")
_step.value_sets = reference_z.value_sets
_step.systems = reference_z.systems
_step.Checker = reference_z.Checker


def setup(run) -> dict:
    from superlu_dist_tpu.utils import platform
    dtype = run.config["options"]["factor_dtype"]
    if platform.complex_needs_cpu(dtype):
        raise harness.Refused(
            f"this program places {dtype} programs on the host CPU "
            "backend, not on the chip the cell is given "
            "(utils/platform.complex_needs_cpu): it cannot run the "
            "configuration " + run.config["name"])
    return _step.setup(run)


reseed = _step.reseed
warm = _step.warm
window = _step.window
check = _step.check
close = _step.close
