"""Resource detection: CPUs, memory, and TPU chips.

TPU-native port of the reference's accelerator-manager protocol
(python/ray/_private/accelerators/accelerator.py:5 AcceleratorManager,
tpu.py:70 TPUAcceleratorManager): count this host's chips from its device
files, name their type from GKE env vars or GCE metadata, expose them as a
first-class ``TPU`` resource plus an accelerator-type resource, and compute
the pod-slice head resource name
(``TPU-<version>-<chips>-head``) used for gang scheduling (tpu.py:330-377).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Optional

# Valid per-host chip counts (reference: tpu.py:14 TPU_VALID_CHIP_OPTIONS).
TPU_VALID_CHIP_OPTIONS = (1, 2, 4, 8)

# GKE TPU env conventions (reference: tpu.py:16-44).
GKE_TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"
GKE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TPU_NAME_ENV = "TPU_NAME"

NUM_CHIPS_OVERRIDE_ENV = "RAY_TPU_NUM_CHIPS"
ACCEL_TYPE_OVERRIDE_ENV = "RAY_TPU_ACCELERATOR_TYPE"


class TPUAcceleratorManager:
    """Detects local TPU chips and manages visibility isolation."""

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips THIS host exposes, counted from its device files (TPU
        VMs expose them as /dev/accel* or, v5e on, /dev/vfio/<n>) without
        touching JAX. The accelerator-type variable is not consulted: it
        names the slice (a host typed v5litepod-4 may expose one chip),
        and a chip that is counted but absent fails the worker pinned to
        it."""
        override = os.environ.get(NUM_CHIPS_OVERRIDE_ENV)
        if override is not None:
            return int(override)
        for pattern in ("/dev/accel*", "/dev/vfio/[0-9]*"):
            devices = glob.glob(pattern)
            if devices:
                return len(devices)
        return 0

    @staticmethod
    def _gce_metadata(path: str) -> Optional[str]:
        """GCE metadata server lookup (reference: tpu.py:14-44 — the
        accelerator-type/topology detection on plain TPU VMs). Short
        timeout + total failure tolerance: off-GCP this must cost ~nothing.
        """
        try:
            import urllib.request
            req = urllib.request.Request(
                "http://metadata.google.internal/computeMetadata/v1/"
                f"instance/attributes/{path}",
                headers={"Metadata-Flavor": "Google"})
            with urllib.request.urlopen(req, timeout=0.5) as r:
                return r.read().decode().strip()
        except Exception:  # lint: broad-except-ok off-GCP the metadata server does not exist; detection degrades to None
            return None

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        override = os.environ.get(ACCEL_TYPE_OVERRIDE_ENV)
        if override:
            return override
        accel_type = os.environ.get(GKE_TPU_ACCELERATOR_TYPE_ENV)
        if accel_type is None and os.environ.get("RAY_TPU_USE_GCE_METADATA"):
            accel_type = TPUAcceleratorManager._gce_metadata(
                "accelerator-type")
        if accel_type:
            # "v5litepod-8" -> "TPU-V5LITEPOD" (reference: tpu.py version
            # parsing + util/accelerators/accelerators.py type constants).
            version = accel_type.split("-", 1)[0].upper()
            return f"TPU-{version}"
        return None

    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple:
        if quantity not in TPU_VALID_CHIP_OPTIONS:
            return (False,
                    f"TPU request must be one of {TPU_VALID_CHIP_OPTIONS}, "
                    f"got {quantity} (reference: tpu.py:14)")
        return (True, None)

    @staticmethod
    def get_current_pod_name() -> Optional[str]:
        return os.environ.get(GKE_TPU_NAME_ENV)

    @staticmethod
    def get_pod_head_resource(accel_type: str, total_chips: int) -> str:
        """Slice-head resource for gang scheduling a pod slice
        (reference: tpu.py:330-377, resource `TPU-<ver>-<chips>-head`)."""
        return f"{accel_type}-{total_chips}-head"


def detect_node_resources(num_cpus: Optional[int] = None,
                          num_tpus: Optional[int] = None,
                          resources: Optional[Dict[str, float]] = None
                          ) -> Dict[str, float]:
    """Build the node's static resource vector (reference: services.py
    resource autodetection feeding the raylet's static resources)."""
    out: Dict[str, float] = {}
    out["CPU"] = float(num_cpus if num_cpus is not None
                       else (os.cpu_count() or 1))
    chips = num_tpus if num_tpus is not None else \
        TPUAcceleratorManager.get_current_node_num_accelerators()
    if chips:
        out["TPU"] = float(chips)
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if accel_type:
            out[accel_type] = float(chips)
    try:
        import psutil  # type: ignore
        out["memory"] = float(psutil.virtual_memory().total)
    except Exception:
        try:
            out["memory"] = float(
                os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
        except (ValueError, OSError):
            pass
    if resources:
        out.update(resources)
    return out


# Chip workers keep XLA's persistent compile cache here unless the
# operator placed it with JAX_COMPILATION_CACHE_DIR. The path is part of
# the cache key's stability: it must not vary between runs.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def tpu_worker_extra_env(chip_ids) -> Dict[str, str]:
    """Full environment for a worker pinned to specific TPU chips —
    shared by the head scheduler and node daemons so ALL chip-environment
    policy lives in one place (reference: tpu.py:170-193 accelerator
    isolation).

    * the chips libtpu may open, as one single-host slice of that shape
      (``get_tpu_ids()`` reads ``TPU_VISIBLE_CHIPS`` back; cpu-pool
      workers get it blanked). libtpu 0.0.34 honours these names and the
      newer ``TPU_VISIBLE_DEVICES``/``TPU_*PROCESS_BOUNDS`` alike;
    * ``JAX_PLATFORMS=tpu``: a worker that was handed chips and cannot
      open them fails — it never computes on the CPU instead;
    * the persistent compile cache: left alone where the operator set
      ``JAX_COMPILATION_CACHE_DIR`` (workers inherit os.environ), else one
      fixed directory in the checkout.
    """
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in sorted(chip_ids)),
        "JAX_PLATFORMS": "tpu",
    }
    bounds = _CHIP_BOUNDS.get(len(chip_ids))
    if bounds:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_COMPILE_CACHE_DIR
    return env
