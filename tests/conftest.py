import gc
import os
import sys

import pytest
from hypothesis import settings

# Make `import repro` work regardless of how pytest is invoked.
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Keep CPU device count at 1 for tests (the 512-device override belongs ONLY
# to launch/dryrun.py, which is exercised via subprocesses).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Property tests must draw the same examples on every run and every machine
# (tier-1 regressions are diffed across commits): load a derandomized
# hypothesis profile.
settings.register_profile("repro-deterministic", derandomize=True,
                          deadline=None, print_blob=False)
settings.load_profile("repro-deterministic")


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_memory():
    """Drop jit/compile caches after every test module.

    The suite compiles hundreds of distinct programs (10 architectures x
    forward/train/decode x kernel sweeps); without this the accumulated
    executables exhaust host RAM late in the run and jaxlib aborts with a
    native bad_alloc."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()
