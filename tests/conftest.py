import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def pack_with_window(monkeypatch):
    """Pack a plan as on a chip whose staging window holds ``window``
    slots, so tests reach the split-block path at small sizes."""
    from repro import platform
    from repro.core import plan as plan_mod

    def pack(plan, window, merge_width=1):
        limits = platform.StageLimits(window=window,
                                      descs=platform.stage_limits().descs)
        with monkeypatch.context() as m:
            m.setattr(plan_mod, "stage_limits", lambda: limits)
            return plan_mod.build_fused_workspace(plan,
                                                  merge_width=merge_width)
    return pack
