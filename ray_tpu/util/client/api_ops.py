"""Named API operations callable locally or through the client server.

Reference parity: the Ray Client server proxies `ray.*` and state/job
API calls for remote drivers (util/client/server/server.py
RayletServicer); this registry is the whitelist of proxied operations —
the CLI uses the same names against either a local runtime or a remote
head (`--address`), so `ray_tpu status` reflects the actual cluster it
points at.
"""

from __future__ import annotations

from typing import Any, Callable, Dict


def registry() -> Dict[str, Callable[..., Any]]:
    import ray_tpu
    from ray_tpu.job import JobSubmissionClient
    from ray_tpu.util import state

    def job_client() -> JobSubmissionClient:
        return JobSubmissionClient()

    return {
        "cluster_resources": ray_tpu.cluster_resources,
        "available_resources": ray_tpu.available_resources,
        "list_nodes": state.list_nodes,
        "list_tasks": state.list_tasks,
        "list_actors": state.list_actors,
        "list_objects": state.list_objects,
        "list_workers": state.list_workers,
        "list_placement_groups": state.list_placement_groups,
        # Graceful drain (docs/DRAIN.md): runs ON the head — the CLI can
        # fire-and-poll a drain against a remote cluster.
        "drain_node": state.drain_node,
        "drain_status": state.drain_status,
        "summarize_tasks": state.summarize_tasks,
        "summarize_actors": state.summarize_actors,
        "summarize_objects": state.summarize_objects,
        "timeline": lambda: state.timeline(filename=None),
        "cluster_metrics": _cluster_metrics,
        # Tracing consumers (PR 7): cross-node trace tree + the merged
        # chrome export, served from the head's span store.
        "get_trace": _get_trace,
        "export_chrome_trace": _export_chrome_trace,
        "profile_actor": _profile_actor,
        "job_submit": lambda **kw: job_client().submit_job(**kw),
        "job_status": lambda job_id: job_client().get_job_status(job_id),
        "job_logs": lambda job_id: job_client().get_job_logs(job_id),
        "job_list": lambda: job_client().list_jobs(),
        "job_stop": lambda job_id: job_client().stop_job(job_id),
        # Serve control plane (reference: serve CLI → controller REST):
        # deploy runs ON the head, so apps outlive the CLI process.
        "serve_deploy": _serve_deploy,
        "serve_status": _serve_status,
        "serve_shutdown": _serve_shutdown,
    }


def _get_trace(trace_id: str) -> dict:
    from ray_tpu.util import tracing
    return tracing.get_trace(trace_id)


def _export_chrome_trace(trace_id=None) -> list:
    from ray_tpu.util import tracing
    return tracing.export_chrome_trace(filename=None, trace_id=trace_id)


def _profile_actor(actor: str, seconds: float) -> dict:
    """A chip-path profile taken inside the worker that hosts `actor`
    (its name, or a prefix of its id as `ray_tpu list actors` shows
    it); the trace's bytes come back under "xplane"."""
    from ray_tpu._private import gcs, state
    from ray_tpu.api import ActorHandle
    from ray_tpu.util import profiling

    hits = [e.spec for e in state.get_node().gcs.actors.list()
            if e.state == gcs.ACTOR_ALIVE
            and (e.spec.name == actor
                 or e.spec.actor_id.hex().startswith(actor))]
    if len(hits) != 1:
        raise ValueError(
            f"{len(hits)} live actors match {actor!r}: give a name or "
            f"an id prefix that `ray_tpu list actors` shows once")
    spec = hits[0]
    return profiling.remote_capture(
        ActorHandle(spec.actor_id, spec.cls_id, spec.method_meta),
        seconds)


def _cluster_metrics() -> str:
    """Federated Prometheus text (telemetry.py): head registry + every
    node's / worker's latest pushed snapshot, node/worker tagged."""
    from ray_tpu._private.telemetry import cluster_metrics_text
    return cluster_metrics_text()


def _serve_deploy(config: dict):
    from ray_tpu.serve import schema as serve_schema
    return serve_schema.deploy_config(
        serve_schema.ServeDeploySchema.from_dict(config))


def _serve_status():
    from ray_tpu import serve
    return serve.status()


def _serve_shutdown():
    from ray_tpu import serve
    serve.shutdown()
    return True
