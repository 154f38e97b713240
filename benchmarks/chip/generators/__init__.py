"""Spike-raster generators of the benchmark, one module per generator.

Each module defines ``generate(key, ticks, cores, neurons_per_core,
**params) -> (ticks, cores, neurons_per_core) bool``, a pure JAX function
of the PRNG key.  They are copies of the program's `repro.traffic`
generators, kept here so that no change to the program can move the
yardstick; `tests/test_chipbench_generators.py` shows that each copy
reproduces the program's generator bit for bit.
"""

import importlib


def load(name: str):
    """The ``generate`` function of generator ``name``."""
    return importlib.import_module(f"{__name__}.{name}").generate
