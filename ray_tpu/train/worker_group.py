"""Worker group: the actors that run train_loop_per_worker.

Reference parity: train/_internal/worker_group.py (WorkerGroup :102 of
RayTrainWorker actors :19) + the execution side of backend_executor.py.
Each worker is a dedicated actor process; `max_concurrency=2` lets the
controller poll reports while the train loop runs (the reference uses a
separate results thread inside the worker, session.py)."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from .. import api
from ..util import profiling, tracing
from ..util.profiling import annotate
from .backend import JaxBackendConfig
from .checkpoint import Checkpoint
from .session import TrainContext, _Session, _set_session


@api.remote(max_concurrency=2)
class TrainWorker:
    """One training process (reference: worker_group.py:19
    RayTrainWorker)."""

    def __init__(self):
        self._session = None
        self._context = None
        self._backend = None
        self._fit_span_id = None

    def setup(self, context: TrainContext, backend_config,
              checkpoint: Optional[Checkpoint],
              dataset_shards: Optional[Dict[str, Any]] = None,
              run_trace: Optional[Dict[str, str]] = None):
        """`run_trace`: the ids this worker's spans hang under, from the
        controller: {"trace_id", "fit", "start_group"}."""
        run_trace = run_trace or {}
        run = tracing.Run(run_trace.get("trace_id"))
        self._fit_span_id = run_trace.get("fit")
        with run.span("ray_tpu.train.worker_setup",
                      run_trace.get("start_group"),
                      rank=context.world_rank):
            self._context = context
            self._backend = backend_config
            self._session = _Session(context, checkpoint, dataset_shards,
                                     run)
            _set_session(self._session)
            if backend_config is not None:
                backend_config.on_start(context)
        return context.world_rank

    def _runtime_is_settled(self) -> bool:
        """Whether nothing the loop could still do would change the jax
        runtime that starts at its first device call: the worker was
        handed chips (its environment fixes platform and chips,
        _private/resources.py tpu_worker_extra_env) and is alone or has
        joined jax.distributed in on_start. A CPU worker's loop may still
        configure jax before its first device call."""
        return bool(api.get_tpu_ids()) and (
            self._context.world_size == 1 or self._backend.init_distributed)

    def run(self, train_fn: Callable, config: Optional[Dict]):
        """Blocking: executes the user loop; reports flow via poll()."""
        import inspect

        session, rank = self._session, self._context.world_rank
        try:
            if isinstance(self._backend, JaxBackendConfig):
                if self._runtime_is_settled():
                    # The loop would pay this start at its first import
                    # and device call; this only gives it a name.
                    with session.run.span("ray_tpu.train.backend_start",
                                          self._fit_span_id) as start:
                        import jax
                        devices = jax.local_devices()
                        start["attributes"] = {
                            "rank": rank, "platform": devices[0].platform,
                            "device_kind": devices[0].device_kind,
                            "local_devices": len(devices)}
                else:
                    # Importing starts no runtime (on_start does the same
                    # for a gang).
                    import jax  # noqa: F401
                # From here on every program this process builds is logged.
                profiling.COMPILES.listen()
            with session.run.span("ray_tpu.train.loop", self._fit_span_id,
                                  rank=rank) as loop:
                session.loop_span_id = loop["span_id"]
                session.want_timeline()
                sig = inspect.signature(train_fn)
                if len(sig.parameters) >= 1:
                    result = train_fn(config or {})
                else:
                    result = train_fn()
            return {"status": "finished", "result": result}
        finally:
            session.finished = True
            session.want_timeline()

    def poll(self):
        """Drain buffered reports (controller calls this periodically):
        {"reports": [...], "timeline": None or this worker's spans and
        compile log, at the moments _Session names}."""
        if self._session is None:
            return {"reports": [], "timeline": None}
        with annotate("ray_tpu.train.poll"):
            return self._session.drain()

    def get_env_info(self):
        import os
        return {"pid": os.getpid()}

    def shutdown_backend(self):
        if self._backend is not None and self._context is not None:
            self._backend.on_shutdown(self._context)
        return True


class WorkerGroup:
    """Driver-side handle on the gang of TrainWorker actors."""

    def __init__(self, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 max_restarts: int = 0):
        opts: Dict[str, Any] = {"max_concurrency": 2}
        res = dict(resources_per_worker)
        if "CPU" in res:
            opts["num_cpus"] = res.pop("CPU")
        if "TPU" in res:
            opts["num_tpus"] = res.pop("TPU")
        if res:
            opts["resources"] = res
        self.workers = [TrainWorker.options(**opts).remote()
                        for _ in range(num_workers)]
        self.num_workers = num_workers

    def setup(self, make_context: Callable[[int], TrainContext],
              backend_config, checkpoint: Optional[Checkpoint],
              dataset_shards: Optional[List[Dict[str, Any]]] = None,
              timeout: float = 120.0,
              run_trace: Optional[Dict[str, str]] = None):
        refs = []
        for rank, w in enumerate(self.workers):
            shards = dataset_shards[rank] if dataset_shards else None
            refs.append(w.setup.remote(
                make_context(rank), backend_config, checkpoint, shards,
                run_trace))
        return api.get(refs, timeout=timeout)

    def run(self, train_fn: Callable, config: Optional[Dict]):
        return [w.run.remote(train_fn, config) for w in self.workers]

    def poll(self, rank: int = 0, timeout: float = 30.0):
        return api.get(self.workers[rank].poll.remote(), timeout=timeout)

    def poll_all(self, timeout: float = 30.0):
        return api.get([w.poll.remote() for w in self.workers],
                       timeout=timeout)

    def shutdown(self, wait_released_s: float = 5.0):
        for w in self.workers:
            try:
                api.kill(w)
            except Exception:
                pass
        # Worker deaths release gang resources ASYNCHRONOUSLY (the recv
        # mux processes each process EOF); an elastic restart that sizes
        # the next gang before the releases land would under-size it.
        # Wait until the gang's dedicated worker processes are gone from
        # the worker table (their death handler releases the resources).
        import time

        from .._private import state as _state
        mine = {w._actor_id.hex() for w in self.workers}
        deadline = time.monotonic() + wait_released_s
        while time.monotonic() < deadline:
            try:
                rows = _state.current().gcs_request("list_workers")
            except Exception:
                return
            if not any(r.get("dedicated_actor") in mine for r in rows):
                # Row removal precedes the release by a few statements in
                # the same death handler; give it a beat.
                time.sleep(0.1)
                return
            time.sleep(0.05)
