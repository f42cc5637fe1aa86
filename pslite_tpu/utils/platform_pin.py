"""Force the JAX CPU backend with N virtual devices.

``JAX_PLATFORMS`` and ``XLA_FLAGS`` are read when jax is imported, so a
process that may already have imported jax also updates the config, before
first backend use.  This is the single shared implementation behind
tests/conftest.py, ``__graft_entry__.dryrun_multichip`` and any CPU-mesh
tooling.
"""

from __future__ import annotations

import os
import re


def pin_cpu(n_devices: int = 8) -> None:
    """Pin the CPU backend with ``n_devices`` virtual devices.

    Must run before jax initializes a backend; raises RuntimeError if a
    non-CPU backend (or too few devices) already initialized.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        # Replace a stale count rather than trusting it.
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, flags
        )
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    # The CPU client runs every device's program on ONE pool of
    # max(cores, devices) threads (XLA reads ``NPROC`` for the cores).  A
    # host callback inside a program (the Pallas TPU interpreter's DMAs and
    # semaphores: ``ops/muon.py`` ``row_apply``) holds its device's thread
    # and needs another for the arrays it is handed, so a program over ALL
    # the devices of a machine with no more cores than devices never ends:
    # keep a spare thread a device.
    spare = 2 * n_devices
    if int(os.environ.get("NPROC") or os.cpu_count() or 1) < spare:
        os.environ["NPROC"] = str(spare)

    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} virtual CPU devices but the backend already "
            f"initialized with {len(devices)} {devices[0].platform!r} "
            f"device(s); call pin_cpu in a fresh process before any jax "
            f"backend use"
        )
