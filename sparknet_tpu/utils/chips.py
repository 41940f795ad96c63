"""The host's TPU chips, as a parent that must stay off JAX can see them.

A chip belongs to one process at a time: a parent that has touched JAX
holds it, and a child that needs it then fails or hangs.  A parent that
starts one child per chip (the serving router) therefore counts chips
from the device nodes, never through ``jax.devices()``, and hands each
child exactly one chip through libtpu's own environment variables.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Optional


def local_chip_count() -> int:
    """TPU chips this host exposes — 0 when ``JAX_PLATFORMS`` rules the
    TPU out (CPU tests and rehearsals) or there is no device node.  v5e
    chips appear as numbered VFIO groups, older generations as
    ``/dev/accel<N>``."""
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def one_chip_env(
    index: int, base: Optional[Dict[str, str]] = None
) -> Dict[str, str]:
    """Environment for a child process that gets chip ``index`` and no
    other: libtpu then builds a 1x1x1 topology of that chip, so N such
    children run side by side on an N-chip host."""
    env = dict(os.environ if base is None else base)
    env.update(
        TPU_VISIBLE_DEVICES=str(index),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_BOUNDS="1,1,1",
        # one libtpu per chip on this host; each needs its own ports
        TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{8476 + index}",
        TPU_MESH_CONTROLLER_PORT=str(8476 + index),
        TPU_RUNTIME_METRICS_PORTS=str(8431 + index),
    )
    return env
