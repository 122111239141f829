"""Worker-side train session: report / get_checkpoint / context.

Reference parity: python/ray/train/_internal/session.py (report :405,672,
get_checkpoint :786, TrainContext). The session is process-global inside a
training worker; `report()` hands metrics+checkpoint to the driver-side
controller through the worker's report buffer.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..util import profiling, tracing
from ..util.profiling import annotate
from .checkpoint import Checkpoint


@dataclass
class TrainContext:
    world_size: int = 1
    world_rank: int = 0
    local_rank: int = 0
    node_rank: int = 0
    trial_name: str = ""
    experiment_name: str = ""
    storage_path: str = ""


class _Session:
    def __init__(self, context: TrainContext,
                 checkpoint: Optional[Checkpoint] = None,
                 dataset_shards: Optional[Dict[str, Any]] = None,
                 run: Optional[tracing.Run] = None):
        self.context = context
        self.restore_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        self.reports: List[Dict] = []
        self.lock = threading.Lock()
        self.finished = False
        # This worker's share of the run's timeline: its spans, and at
        # three moments (the loop entered, the first report, the loop
        # ended) a copy of them with the process's compile log for the
        # controller's next poll to carry.
        self.run = run or tracing.Run()
        self.loop_span_id: Optional[str] = None
        self._reported = False
        self._timeline_due = False

    def report(self, metrics: Dict, checkpoint: Optional[Checkpoint]):
        if not self._reported:
            self._reported = True
            self.run.mark("ray_tpu.train.first_report", self.loop_span_id)
            self.want_timeline()
        with self.lock:
            self.reports.append({
                "metrics": dict(metrics),
                "checkpoint": checkpoint,
            })

    def want_timeline(self) -> None:
        with self.lock:
            self._timeline_due = True

    def drain(self) -> Dict[str, Any]:
        """What a poll carries: the reports since the last one and, if
        one is due, the timeline (else None)."""
        with self.lock:
            reports, self.reports = self.reports, []
            due, self._timeline_due = self._timeline_due, False
        timeline = None
        if due:
            timeline = {"pid": os.getpid(), "spans": self.run.snapshot(),
                        "compile_log": profiling.COMPILES.entries(),
                        "dropped": profiling.COMPILES.dropped}
        return {"reports": reports, "timeline": timeline}


_session: Optional[_Session] = None


def _set_session(s: Optional[_Session]):
    global _session
    _session = s


def _get_session() -> _Session:
    if _session is None:
        raise RuntimeError(
            "Not inside a training worker; train.report()/get_checkpoint() "
            "only work inside train_loop_per_worker.")
    return _session


# -- public api (reference: ray.train.report / get_checkpoint / ...) -------
def report(metrics: Dict, *, checkpoint: Optional[Checkpoint] = None):
    """Report metrics (+ optional checkpoint) to the controller
    (reference: session.py:405)."""
    with annotate("ray_tpu.train.report"):
        _get_session().report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    """Latest checkpoint to resume from (reference: session.py:786)."""
    return _get_session().restore_checkpoint


def get_context() -> TrainContext:
    return _get_session().context


def get_dataset_shard(name: str = "train"):
    """This worker's dataset shard (reference: session get_dataset_shard)."""
    return _get_session().dataset_shards.get(name)


def get_world_size() -> int:
    return _get_session().context.world_size


def get_world_rank() -> int:
    return _get_session().context.world_rank
