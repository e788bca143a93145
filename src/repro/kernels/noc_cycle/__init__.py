"""Fused wormhole-cycle kernel: the whole xsim cycle as one Pallas launch.

Three files: ``ref.py`` is the bit-exact jnp cycle over packed
router-centric planes (the default engine on every platform),
``noc_cycle.py`` the Pallas chunk kernel running the same ``cycle_core``
with state resident across an inner ``fori_loop``, ``ops.py`` the backend
dispatch (``ref`` / ``pallas`` / ``pallas_interpret``).
"""
from .noc_cycle import make_chunk_runner
from .ops import CTR, CycleState, init_planes, resolve_backend, run_cycles
from .ref import TABLE_FIELDS, cycle_core

__all__ = [
    "CTR", "CycleState", "TABLE_FIELDS", "cycle_core", "init_planes",
    "make_chunk_runner", "resolve_backend", "run_cycles",
]
