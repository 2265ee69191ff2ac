"""memroof: a memory-access-pattern-aware JAX training/serving framework.

Reproduction of "Optimizing Memory Performance of Xilinx FPGAs under Vitis"
(CS.DC 2020), adapted to the TPU memory hierarchy.  See DESIGN.md.
"""
__version__ = "1.0.0"

# the closed tune->execute loop is part of the public surface:
# ``import repro; repro.tune.plan_for(...)``
from repro import tune  # noqa: F401
