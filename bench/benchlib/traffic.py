"""The one traffic generator: it reads a mix's parameters (a JSON file under
``bench/traffic``) and a seed, and returns the schedule the window drives.

A ``kernel_calls`` mix issues calls back to back; the seed orders the
input sets the calls take, and never changes the amount of work.
"""
from __future__ import annotations

import numpy as np


def call_order(mix, seed, n_calls):
    """Back-to-back kernel calls: which of the ``input_sets`` each call
    takes (a seeded permutation, repeated)."""
    k = int(mix["input_sets"])
    perm = np.random.default_rng(seed).permutation(k)
    return [int(perm[i % k]) for i in range(n_calls)]
