import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def fresh_jit():
    """A fault patched under a jitted program needs that program traced anew."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def break_step(monkeypatch, fresh_jit):
    """``break_step(fault)`` breaks the program's slot step underneath a run:
    ``"unchanged"`` returns the state it was given, ``"altered"`` scales the
    backlog sample the step reports."""
    from repro.core import cohort_fused, compact

    step = compact.compact_slot_step

    def apply(fault):
        def broken(c, state, xs, **kw):
            new, out = step(c, state, xs, **kw)
            if fault == "unchanged":
                return state, out
            if fault == "altered":  # the backlog sample, as the step reports it
                return new, (out[0] * 1.001,) + tuple(out[1:])
            raise ValueError(fault)

        monkeypatch.setattr(cohort_fused, "compact_slot_step", broken)

    return apply
