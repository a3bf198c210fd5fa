"""orleans_tpu — a TPU-native virtual-actor ("grain") framework.

A ground-up re-design of the Microsoft Orleans programming model
(reference at /root/reference, surveyed in SURVEY.md) for TPU hardware:
grain invocations are coalesced each tick into vectorized actor-update
kernels (jax/pjit/Pallas) over activation state sharded across the device
mesh, with cross-silo messages riding ICI collectives and the host running
the control plane (membership, placement, storage, client gateway).
"""

__version__ = "0.1.0"

