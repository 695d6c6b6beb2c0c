"""Device code for the framework's hot spots.

Each kernel directory has kernel.py (the device program: a
``pl.pallas_call`` with BlockSpec VMEM tiling, or XLA code where the
data lives in HBM — ``scan``, ``art_probe``), ops.py (the host wrapper),
and ref.py (the oracle it is validated against).  ``backend.interpret``
is the one interpret-or-compile decision for every Pallas kernel."""
