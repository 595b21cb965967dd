"""The control of the correctness check: the program one precision lower.

The configurations state float32 at HIGHEST, which the program pins in its
contractions through module constants (``traffic.HIGHEST`` for the flow
and Anderson contractions, ``marginals.HIGHEST`` for the marginal link
term, ``batched_solve._HIGHEST`` inside the kernels).  The control sets
them to the next precision below, ``HIGH`` (three bf16 passes), from the
outside, with no switch in the program: the step a later change might take
for speed.  A run under the control has to come out not correct.
"""

from __future__ import annotations

import contextlib

TARGETS = (("repro.core.traffic", "HIGHEST"),
           ("repro.core.marginals", "HIGHEST"),
           ("repro.kernels.batched_solve", "_HIGHEST"))


@contextlib.contextmanager
def lowered_precision():
    import importlib

    import jax

    saved = []
    for mod_name, attr in TARGETS:
        mod = importlib.import_module(mod_name)
        saved.append((mod, attr, getattr(mod, attr)))
        setattr(mod, attr, jax.lax.Precision.HIGH)
    jax.clear_caches()
    try:
        yield
    finally:
        for mod, attr, value in saved:
            setattr(mod, attr, value)
        jax.clear_caches()
