"""The paper's primary contribution: operator-level workload characterization.

hlotext      — HLO parsing: collective inventory, op taxonomy, fusion counts
roofline     — DeviceSpec + three-term roofline over compiled dry-run artifacts
analytical   — closed-form Table-3-style op inventory per architecture
characterize — paper-style runtime breakdowns (Figs 4/5/9/10) on a DeviceSpec
distmodel    — analytical DP/MP multi-device profiles (Fig 12, paper §4.1.1)
spans        — host spans on the profiler's clock (the serving engine's phases)
"""
from . import analytical, characterize, distmodel, hlotext, roofline, spans

__all__ = ["analytical", "characterize", "distmodel", "hlotext", "roofline",
           "spans"]

