"""The traffic generators: one module per kind of traffic.

A traffic mix is a data file, ``traffic/<mix>.json``, whose ``driver``
names the module here that reads it.  Each module has ``run(c, mix, *,
seed, seconds, trace, t_start, devs, variant)``, which drives one run of a
cell and returns its readings (see ``harness.run_cell``).  A new kind of
traffic is a new module; a new mix of an existing kind is a new data file.
"""
