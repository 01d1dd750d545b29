"""Faults planted under a cell's timed path, to show its comparison catches them.

Each fault breaks the front-end call that a cell's window drives, where the
answer is produced:

* ``unchanged``: the call answers every request with its first answer, as a
  step that returns its state unchanged would;
* ``half``: half of the training data is left out;
* ``altered``: an answer is changed where it is produced.

``planted(workload, kind)`` patches the program for the duration of a
``with`` block.  ``bench/control.py --fault`` runs a cell with one planted,
on the chip at the cell's own size; the benchmark's tests run each at a
tiny size.
"""

from __future__ import annotations

import contextlib


def _stale(orig):
    memo = {}

    def stale(self, *a, **k):
        if "r" not in memo:
            memo["r"] = orig(self, *a, **k)
        return memo["r"]

    return stale


def _gp_half(orig):
    def half(self, xt):
        from repro.core import GaussianProcess

        sub = GaussianProcess(self.x_train[::2], self.y_train[::2], params=self.params,
                              tile_size=self.tile_size, kernel=self.kernel)
        return orig(sub, xt)

    return half


def _gp_altered(orig):
    def altered(self, xt):
        mean, var = orig(self, xt)
        return mean + 0.01, var

    return altered


def _fleet_half(orig):
    def half(self, idx, cap):
        xs, ys, nv = orig(self, idx, cap)
        return xs, ys, nv - nv // 2

    return half


def _fleet_altered(orig):
    def altered(self, tests, **k):
        out = orig(self, tests, **k)
        m, c = out[-1]
        out[-1] = (m + 0.05, c)
        return out

    return altered


# workload -> kind -> (front-end class name, method, wrapper of the method)
FAULTS = {
    "msd_16k.posterior": {
        "unchanged": ("GaussianProcess", "predict_with_uncertainty", _stale),
        "half": ("GaussianProcess", "predict_with_uncertainty", _gp_half),
        "altered": ("GaussianProcess", "predict_with_uncertainty", _gp_altered),
    },
    "arbo_fleet.refit": {
        "unchanged": ("GPFleet", "predict_each", _stale),
        "half": ("GPFleet", "_stack", _fleet_half),
        "altered": ("GPFleet", "predict_each", _fleet_altered),
    },
}


@contextlib.contextmanager
def planted(workload: str, kind: str):
    """The program with fault ``kind`` of ``workload`` planted, restored on exit."""
    from repro.core import gp

    cls_name, method, wrap = FAULTS[workload][kind]
    owner = getattr(gp, cls_name)
    orig = getattr(owner, method)
    setattr(owner, method, wrap(orig))
    try:
        yield
    finally:
        setattr(owner, method, orig)
