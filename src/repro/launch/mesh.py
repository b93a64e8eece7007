"""TPU v5e hardware constants, re-exported from the topology layer.

The 16×16 pod shape and the constants that used to be hard-coded here
live in :mod:`repro.runtime.topology` now (the dry-run builds its pod
meshes from the ``tpu_pod`` / ``tpu_pod_x2`` presets); this module stays
importable because benchmarks/roofline.py pulls the constants.
"""

from __future__ import annotations

from repro.runtime.topology import (  # noqa: F401  (re-exports)
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    TOPOLOGY_PRESETS,
)
