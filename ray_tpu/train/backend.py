"""Training backends: per-framework worker-group setup hooks.

Reference parity: train/_internal/backend_executor.py Backend hooks —
`_TorchBackend.on_start` runs dist.init_process_group (torch/config.py:156),
the TF backend writes TF_CONFIG, the torch-XLA backend sets XLA env vars
(torch/xla/config.py:20,120). The TPU-native `JaxBackend.on_start` replaces
all of that with the jax.distributed runtime + (optionally) a device mesh:
the DEVICE-COLLECTIVE BOUNDARY of SURVEY.md §3.4 becomes mesh construction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass
class BackendConfig:
    """Base backend config (reference: train/backend.py BackendConfig)."""

    def backend_name(self) -> str:
        return "noop"

    def on_start(self, context) -> None:
        """Runs INSIDE each training worker before the train loop."""

    def on_shutdown(self, context) -> None:
        pass


@dataclass
class JaxBackendConfig(BackendConfig):
    """Brings up the jax distributed runtime across the worker group
    (replacing `dist.init_process_group(nccl|gloo)`, torch/config.py:115).

    After on_start, `jax.devices()` inside every worker spans the whole
    group — each worker contributes its local devices and data-parallel
    training proceeds by mesh sharding, not gradient hooks — or on_start
    has raised: a group whose runtimes stayed isolated (one-chip workers
    on one TPU host) never passes for a data-parallel one.
    """

    coordinator_port: Optional[int] = None
    group_name: str = "train"
    init_distributed: bool = True

    def backend_name(self) -> str:
        return "jax"

    def on_start(self, context) -> None:
        if not self.init_distributed or context.world_size <= 1:
            return
        from ..util.collective.collective_group.xla_collective_group import (
            _rendezvous,
            ensure_distributed,
        )
        group = f"{self.group_name}/{context.experiment_name}"
        coordinator = _rendezvous(group, context.world_size,
                                  context.world_rank)
        ensure_distributed(coordinator, context.world_size,
                           context.world_rank)
        import jax
        expected = context.world_size * jax.local_device_count()
        if jax.device_count() != expected:
            # jax.distributed joins CPU processes over gRPC, but it does
            # not join TPU runtimes: workers pinned to separate chips of
            # one host each keep a runtime of their own, and a "data
            # parallel" step there would be world_size unsynchronised
            # replicas. Refuse rather than train that way.
            raise RuntimeError(
                f"JaxBackendConfig: {context.world_size} workers joined "
                f"jax.distributed but this worker's runtime holds "
                f"{jax.device_count()} device(s), not {expected}: the "
                f"workers' {jax.devices()[0].platform} runtimes are "
                "isolated. Give one worker all chips of the host "
                "(resources_per_worker={'TPU': n}) and shard over a "
                "mesh, or pass init_distributed=False for independent "
                "replicas.")


@dataclass
class TorchBackendConfig(BackendConfig):
    """torch.distributed process group over gloo for CPU-side torch code
    (reference: train/torch/config.py TorchConfig). Kept for users moving
    host-side torch data pipelines; device math belongs to jax."""

    backend: str = "gloo"
    init_method: str = "tcp"

    def backend_name(self) -> str:
        return "torch"

    def on_start(self, context) -> None:
        if context.world_size <= 1:
            return
        import torch.distributed as dist

        if dist.is_initialized():
            return
        from ..util.collective.collective_group.xla_collective_group import (
            _rendezvous,
        )
        addr = _rendezvous(f"torch/{context.experiment_name}",
                           context.world_size, context.world_rank)
        host, port = addr.rsplit(":", 1)
        dist.init_process_group(
            backend=self.backend,
            init_method=f"tcp://{host}:{port}",
            world_size=context.world_size,
            rank=context.world_rank)


@dataclass
class TensorflowBackendConfig(BackendConfig):
    """Writes TF_CONFIG across the worker group (reference:
    train/tensorflow/config.py:24-37 _setup_tensorflow_environment →
    MultiWorkerMirroredStrategy). Each worker publishes host:port via the
    GCS KV, waits for the full roster, and exports the standard TF_CONFIG
    JSON; tf.distribute picks it up from there."""

    timeout_s: float = 60.0

    def backend_name(self) -> str:
        return "tensorflow"

    def on_start(self, context) -> None:
        if context.world_size <= 1:
            return
        import json
        import os
        import time

        from ..util.collective.collective_group.xla_collective_group import (
            _free_port,
            _kv_get,
            _kv_put,
        )
        # context.experiment_name embeds a fresh per-attempt uid
        # (controller.py make_context), so restarted groups never read a
        # previous attempt's roster keys.
        group = f"tf/{context.experiment_name}"
        addr = f"127.0.0.1:{_free_port()}"
        _kv_put(f"{group}/addr/{context.world_rank}", addr.encode())
        roster = [None] * context.world_size
        deadline = time.monotonic() + self.timeout_s
        while time.monotonic() < deadline:
            for r in range(context.world_size):
                if roster[r] is None:
                    raw = _kv_get(f"{group}/addr/{r}")
                    if raw:
                        roster[r] = raw.decode()
            if all(roster):
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"TF_CONFIG roster incomplete after {self.timeout_s}s: "
                f"{roster}")
        os.environ["TF_CONFIG"] = json.dumps({
            "cluster": {"worker": roster},
            "task": {"type": "worker", "index": context.world_rank},
        })


@dataclass
class HorovodBackendConfig(BackendConfig):
    """Reference: train/horovod/config.py HorovodConfig. Horovod is a
    torch/TF allreduce runtime not present in this image (and redundant on
    TPU, where XLA emits the collectives); the config gates with guidance
    rather than silently no-op."""

    def backend_name(self) -> str:
        return "horovod"

    def on_start(self, context) -> None:
        try:
            import horovod  # noqa: F401
        except ImportError:
            raise ImportError(
                "horovod is not installed in this environment. On TPU use "
                "JaxBackendConfig (XLA emits the allreduce) or "
                "TorchBackendConfig (gloo) for host-side torch code."
            ) from None
        import horovod.torch as hvd
        hvd.init()
