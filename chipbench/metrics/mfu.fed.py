"""Model FLOP/s utilisation of the launcher cell, in percent (as ``mfu``,
from ``fed_pairs_per_s``)."""

from chipbench.metrics import mfu


def read(run):
    return mfu.read(run, "fed_pairs_per_s")
