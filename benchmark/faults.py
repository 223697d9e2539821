"""Faults planted under the timed path, each breaking a guarantee the
deployment states. `control.py` runs a cell with `skip_verification`,
the control; the tests run every fault at a small size and see the
comparison fail.

Each patches the device engine's host-side entry points, so the
compiled programs, and their cached executables, stay as they are.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(name: str, make):
    from janus_tpu.aggregator.engine_cache import EngineCache

    orig = getattr(EngineCache, name)
    setattr(EngineCache, name, make(orig))
    try:
        yield
    finally:
        setattr(EngineCache, name, orig)


def skip_verification():
    """The control: the helper accepts every report that decodes, as if
    the joint FLP verification had passed."""

    def make(orig):
        def helper_init(self, *args):
            out1, mask, prep_msg = orig(self, *args)
            ok_mask = np.asarray(args[-1], dtype=bool)[: len(mask)]
            return out1, ok_mask, prep_msg

        return helper_init

    return _patched("helper_init", make)


def unchanged_state():
    """Accumulation returns the aggregate share it started from (zero)."""

    def make(orig):
        def aggregate(self, out_shares, mask):
            return [0] * len(orig(self, out_shares, mask))

        return aggregate

    return _patched("aggregate", make)


def half_batch():
    """Accumulation leaves out the second half of each job's reports."""

    def make(orig):
        def aggregate(self, out_shares, mask):
            mask = np.array(mask, dtype=bool)
            mask[len(mask) // 2 :] = False
            return orig(self, out_shares, mask)

        return aggregate

    return _patched("aggregate", make)


def altered_answer():
    """Each aggregate share is altered where it is produced: its first
    element is off by one."""

    def make(orig):
        def aggregate(self, out_shares, mask):
            share = list(orig(self, out_shares, mask))
            share[0] = (share[0] + 1) % self.p3.jf.MODULUS
            return share

        return aggregate

    return _patched("aggregate", make)


FAULTS = {
    "skip_verification": skip_verification,
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "altered_answer": altered_answer,
}
