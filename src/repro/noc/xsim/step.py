"""xsim's per-cycle engine — now the fused ``kernels.noc_cycle`` kernel.

The old per-worm slot pool (``SlotState``: ``sfpos[K, F]`` flit stages,
slot allocation by cumsum/searchsorted, two ``kernels.noc_step`` segmented
-min rounds and ~20 masked scatters per cycle) is gone. State lives in
packed router-centric planes — per-(link, VC) FIFO ownership plus NI lane
fronts — where both arbitration rounds are dense masked mins over each
node's static input-port table and the *only* scatter left is the
(L,)-sized delivery recording. See ``kernels/noc_cycle/ref.py`` and
DESIGN.md §8 for the layout and the fusion boundaries.

Consequences surfaced here:

* No slot pool: capacity is structural (a worm in flight holds a VC FIFO
  or an NI lane front), so there is no ``K`` to size, no overflow, and no
  regrow-and-rerun loop in the runner.
* ``backend=`` selects the whole-cycle engine now, not just arbitration:
  ``ref`` (jnp scan — the default, and the engine that compiles for TPU),
  ``pallas`` (fused chunk kernel; Mosaic does not lower it yet),
  ``pallas_interpret`` (kernel semantics on CPU, bit-identical to ``ref``
  — CI's validation path). It threads from ``NoCConfig.
  xsim_backend`` through ``xsimulate`` down to ``run_cycles``.
* DPM children inject in dynamic parent-arrival order (the host sim's
  release-order queues), closing the old static-order fidelity delta.

This module keeps the xsim-side surface: ``CTR`` counter names and the
``run_cycles`` entry point the batch runner scans with.
"""
from __future__ import annotations

from ...kernels.noc_cycle import (  # noqa: F401  (re-exports)
    CTR,
    CycleState,
    cycle_core,
    init_planes,
    run_cycles,
)

__all__ = ["CTR", "CycleState", "cycle_core", "init_planes", "run_cycles"]
