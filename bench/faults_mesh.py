"""Faults that only a cell on more than one chip can have, beside
``bench.faults``'s sync faults; each patches the program in this process
and restores it on exit.  ``FAULTS`` is what ``bench/drivers/sync_mesh.py``
plants when ``bench/calibrate.py --faults`` names one."""
from __future__ import annotations

import contextlib

from bench import faults


@contextlib.contextmanager
def exchange_dropped():
    """The eq. 10 mean taken without the exchange between chips: each chip
    divides its own rows' weighted sum by its own weight sum and keeps
    that, so every chip's rows go on from a mean over its own UEs only."""
    from repro.fl import aggregate

    def local_mean(num, den, axis):
        return num / den
    with faults._patched(aggregate, "psum_weighted_mean", local_mean):
        yield


FAULTS = dict(faults.FAULTS["sync"], exchange_dropped=exchange_dropped)
