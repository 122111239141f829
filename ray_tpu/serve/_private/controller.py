"""ServeController: the control-plane actor.

Reference: python/ray/serve/_private/controller.py:84 (ServeController) +
deployment_state.py / application_state.py (reconciliation) +
autoscaling_state.py (replica autoscaling). One async actor owns desired
state (applications -> deployments -> target replica counts), runs a
reconcile loop that starts/stops/heals replica actors, and broadcasts
replica membership + routes to routers/proxies over long-poll
(long_poll.py). The request path never touches this actor.
"""
import asyncio
import time
from typing import Any, Dict, List, Optional

import ray_tpu
from .long_poll import LongPollHost
from .replica import start_replica

CONTROLLER_NAME = "SERVE_CONTROLLER"


class _DeploymentState:
    def __init__(self, info: Dict[str, Any]):
        self.info = info                  # config fields, cls_blob, args
        self.replicas: List = []          # live actor handles
        self.replica_seq = 0              # monotonic replica name suffix
        self.target = info["initial_replicas"]
        # actor id -> future of the replica's first check_health call,
        # which the actor runs once its constructor has returned. Until
        # it completes the replica is STARTING, not unhealthy: a model
        # that takes a minute to load onto a chip must not be replaced
        # every health_check_timeout_s (reference: deployment_state.py
        # ReplicaState.STARTING).
        self.starting: Dict[Any, "asyncio.Future"] = {}
        self.last_upscale_ok_t = 0.0      # autoscaling decision debounce
        self.last_downscale_ok_t = 0.0


class ServeController:
    """Async controller actor (reference: controller.py:84)."""

    def __init__(self):
        self._apps: Dict[str, List[str]] = {}           # app -> deployments
        self._deployments: Dict[str, _DeploymentState] = {}
        self._routes: Dict[str, tuple] = {}             # prefix -> (app, dep)
        self._long_poll = LongPollHost()
        self._shutdown = False
        # Per-node proxy reconciliation (reference: proxy_state.py
        # ProxyStateManager): node_hex -> (actor_handle, (host, port)).
        self._proxies: Dict[str, tuple] = {}
        self._proxy_config: Optional[Dict[str, Any]] = None
        self._proxy_errors: Dict[str, str] = {}
        # The reconcile task is started lazily from the first async method:
        # __init__ runs on the worker's main thread, while async actor
        # methods run on the dedicated actor event loop (worker_proc.py
        # _ensure_actor_loop) — the task must live on that loop.
        self._loop_task = None

    def _ensure_loop_task(self):
        if self._loop_task is None or self._loop_task.done():
            if not self._shutdown:
                self._loop_task = asyncio.get_event_loop().create_task(
                    self._reconcile_loop())

    # -- API used by serve.run / handles / proxy ---------------------------
    async def deploy_application(self, app_name: str,
                                 deployments: List[Dict[str, Any]],
                                 route_prefix: Optional[str],
                                 ingress: str) -> bool:
        """Reference: application_state.py apply_app_config."""
        self._ensure_loop_task()
        old = set(self._apps.get(app_name, []))
        new_names = []
        for dep in deployments:
            name = dep["name"]
            new_names.append(name)
            existing = self._deployments.get(name)
            if existing is not None and self._same_target(existing.info, dep):
                # In-place update: user_config / replica count only.
                existing.info.update(dep)
                if dep.get("autoscaling_config") is None:
                    existing.target = dep["initial_replicas"]
                if dep.get("user_config") is not None:
                    for r in existing.replicas:
                        r.reconfigure.remote(dep["user_config"])
                continue
            if existing is not None:
                await self._stop_deployment(name)
            self._deployments[name] = _DeploymentState(dep)
        for stale in old - set(new_names):
            await self._stop_deployment(stale)
            self._deployments.pop(stale, None)
        self._apps[app_name] = new_names
        if route_prefix is not None:
            self._routes[route_prefix] = (app_name, ingress)
            self._long_poll.notify_changed("routes", dict(self._routes))
        await self._reconcile_once()
        return True

    @staticmethod
    def _same_target(old_info: Dict, new_info: Dict) -> bool:
        return (old_info["cls_blob"] == new_info["cls_blob"]
                and old_info["init_args"] == new_info["init_args"]
                and old_info["init_kwargs"] == new_info["init_kwargs"]
                and old_info["actor_options"] == new_info["actor_options"])

    async def delete_application(self, app_name: str) -> bool:
        self._ensure_loop_task()
        for name in self._apps.pop(app_name, []):
            await self._stop_deployment(name)
            self._deployments.pop(name, None)
        self._routes = {p: v for p, v in self._routes.items()
                        if v[0] != app_name}
        self._long_poll.notify_changed("routes", dict(self._routes))
        return True

    async def graceful_shutdown(self) -> bool:
        self._shutdown = True
        for name in list(self._deployments):
            await self._stop_deployment(name)
        self._deployments.clear()
        self._apps.clear()
        for node_hex, (handle, _addr) in list(self._proxies.items()):
            try:
                ray_tpu.kill(handle)
            except Exception:
                pass
        self._proxies.clear()
        return True

    async def listen_for_change(self, snapshot_ids: Dict[str, int],
                                timeout_s: float = 30.0):
        self._ensure_loop_task()
        return await self._long_poll.listen_for_change(snapshot_ids,
                                                       timeout_s)

    async def get_replica_snapshot(self, deployment: str) -> List:
        self._ensure_loop_task()
        st = self._deployments.get(deployment)
        return list(st.replicas) if st else []

    async def get_route_table(self) -> Dict[str, tuple]:
        self._ensure_loop_task()
        return dict(self._routes)

    async def list_deployments(self) -> Dict[str, Dict[str, Any]]:
        self._ensure_loop_task()
        return {
            name: {"target_replicas": st.target,
                   "live_replicas": len(st.replicas),
                   "app": next((a for a, ds in self._apps.items()
                                if name in ds), None)}
            for name, st in self._deployments.items()
        }

    async def drain_node(self, node_id_hex: str) -> int:
        """Pull every replica living on `node_id_hex` out of routing,
        wait for their in-flight requests to finish, then stop them.

        Order matters for the zero-failed-requests guarantee: routers
        learn the shrunken membership over long-poll *before* any
        replica dies, so no new request is dispatched to a victim, and
        victims are only killed once their queue reports empty.
        Replacement replicas come back via the ordinary reconcile loop
        (the scheduler refuses draining nodes, so they land elsewhere).
        """
        self._ensure_loop_task()
        loop = asyncio.get_event_loop()
        try:
            from ray_tpu.util.state import list_actors
            rows = await loop.run_in_executor(None, list_actors)
        except Exception:  # lint: broad-except-ok state API unreachable -> nothing to map, drain 0
            rows = []
        on_node = {r["actor_id"] for r in rows
                   if r.get("node_id") == node_id_hex}
        victims = []
        for name, st in self._deployments.items():
            keep = [r for r in st.replicas
                    if r._actor_id.hex() not in on_node]
            drop = [r for r in st.replicas
                    if r._actor_id.hex() in on_node]
            if drop:
                st.replicas = keep
                self._long_poll.notify_changed(
                    f"replicas::{name}", list(st.replicas))
                victims.extend(drop)
        drained = 0
        for v in victims:
            # Wait until the replica is idle, then require one more
            # empty reading after a short settle so a request that a
            # router dispatched just before it saw the long-poll update
            # is not raced by the kill.
            try:
                while True:
                    if await v.get_queue_len.remote() == 0:
                        await asyncio.sleep(0.2)
                        if await v.get_queue_len.remote() == 0:
                            break
                    else:
                        await asyncio.sleep(0.05)
            except Exception:  # lint: broad-except-ok replica already dead: nothing in flight
                pass
            try:
                ray_tpu.kill(v)
            except Exception:  # lint: broad-except-ok racing actor death; kill is idempotent
                pass
            drained += 1
        return drained

    # -- reconciliation ----------------------------------------------------
    async def _stop_deployment(self, name: str):
        st = self._deployments.get(name)
        if st is None:
            return
        for r in st.replicas:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass
        st.replicas = []
        st.starting.clear()
        self._long_poll.notify_changed(f"replicas::{name}", [])

    def _start_one(self, name: str, st: _DeploymentState):
        info = st.info
        st.replica_seq += 1
        return start_replica(
            name, st.replica_seq, info["cls_blob"], info["init_args"],
            info["init_kwargs"], info["actor_options"],
            info["max_ongoing_requests"], info.get("user_config"))

    async def _reconcile_once(self):
        for name, st in self._deployments.items():
            changed = False
            while len(st.replicas) < st.target:
                replica = self._start_one(name, st)
                st.starting[replica._actor_id] = asyncio.ensure_future(
                    replica.check_health.remote())
                st.replicas.append(replica)
                changed = True
            while len(st.replicas) > st.target:
                victim = st.replicas.pop()
                st.starting.pop(victim._actor_id, None)
                try:
                    ray_tpu.kill(victim)
                except Exception:
                    pass
                changed = True
            if changed:
                self._long_poll.notify_changed(
                    f"replicas::{name}", list(st.replicas))

    async def _health_and_autoscale(self):
        now = time.monotonic()
        for name, st in self._deployments.items():
            # Health: replace dead replicas (reference:
            # deployment_state.py check_and_update_replicas).
            alive, dead = [], []
            for r in st.replicas:
                started = st.starting.get(r._actor_id)
                if started is not None and not started.done():
                    alive.append(r)  # constructor still running
                    continue
                try:
                    if started is not None:
                        del st.starting[r._actor_id]
                        ok = started.result()
                    else:
                        ok = await asyncio.wait_for(
                            r.check_health.remote(),
                            timeout=st.info["health_check_timeout_s"])
                except Exception:  # lint: broad-except-ok any failure of the health call (actor died, constructor raised, timeout) means the replica is replaced
                    ok = False
                (alive if ok else dead).append(r)
            for r in dead:
                # A replica given up on must release what it holds (its
                # chip, above all) before its replacement can be placed.
                try:
                    ray_tpu.kill(r)
                except Exception:  # lint: broad-except-ok racing actor death; kill is idempotent
                    pass
            if dead:
                st.replicas = alive
                self._long_poll.notify_changed(
                    f"replicas::{name}", list(st.replicas))
            # Autoscale on total ongoing requests (reference:
            # autoscaling_policy.py replica-count policy).
            cfg = st.info.get("autoscaling_config")
            if cfg is None or not st.replicas:
                continue
            try:
                lens = await asyncio.gather(
                    *[r.get_queue_len.remote() for r in st.replicas])
            except Exception:
                continue
            desired = cfg.desired_replicas(float(sum(lens)),
                                           len(st.replicas))
            if desired > st.target:
                if st.last_upscale_ok_t == 0.0:
                    st.last_upscale_ok_t = now
                if now - st.last_upscale_ok_t >= cfg.upscale_delay_s:
                    st.target = desired
                    st.last_upscale_ok_t = 0.0
                st.last_downscale_ok_t = 0.0
            elif desired < st.target:
                if st.last_downscale_ok_t == 0.0:
                    st.last_downscale_ok_t = now
                if now - st.last_downscale_ok_t >= cfg.downscale_delay_s:
                    st.target = desired
                    st.last_downscale_ok_t = 0.0
                st.last_upscale_ok_t = 0.0
            else:
                st.last_upscale_ok_t = st.last_downscale_ok_t = 0.0

    # -- per-node proxies --------------------------------------------------
    async def configure_proxies(self, host: str = "0.0.0.0",
                                port: int = 0) -> bool:
        """Enable per-node ingress: the reconcile loop keeps one
        ProxyReplica actor on every alive non-head node (the driver's
        in-process proxy covers the head). Reference: proxy_state.py
        ProxyStateManager.update()."""
        self._ensure_loop_task()
        self._proxy_config = {"host": host, "port": port}
        await self._reconcile_proxies()
        return True

    async def get_proxy_table(self) -> Dict[str, tuple]:
        """node_hex -> (host, port) for every live node proxy."""
        self._ensure_loop_task()
        return {n: addr for n, (_h, addr) in self._proxies.items()
                if addr is not None}

    async def _reconcile_proxies(self):
        if self._proxy_config is None:
            return
        import traceback

        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        from ray_tpu.util.state import list_nodes
        loop = asyncio.get_event_loop()
        try:
            nodes = await loop.run_in_executor(None, list_nodes)
        except Exception:
            self._proxy_errors["_list_nodes"] = traceback.format_exc()
            return
        rows = [n for n in nodes
                if n.get("alive", True) and not n.get("is_head")
                and not n.get("draining")]
        alive = {n["node_id"] for n in rows}
        # The head records each daemon's reachable peer IP at
        # registration; a proxy bound to 0.0.0.0 must be advertised at
        # THAT address, not its bind address.
        node_host = {n["node_id"]: n.get("host") for n in rows}
        # Drop proxies on dead nodes; health-check the rest.
        for node_hex in list(self._proxies):
            handle, _addr = self._proxies[node_hex]
            if node_hex not in alive:
                self._proxies.pop(node_hex, None)
                try:
                    ray_tpu.kill(handle)
                except Exception:
                    pass
                continue
        # Health: a proxy whose server thread died serves
        # connection-refused; replace it (reference: proxy_state.py
        # proxy health states).
        for node_hex, (handle, _addr) in list(self._proxies.items()):
            try:
                ok = await asyncio.wait_for(handle.check_health.remote(),
                                            timeout=15)
            except Exception:
                ok = False
            if not ok:
                self._proxies.pop(node_hex, None)
                try:
                    ray_tpu.kill(handle)
                except Exception:
                    pass
        for node_hex in alive:
            if node_hex in self._proxies:
                continue
            from .proxy import ProxyReplica
            name = f"SERVE_PROXY::{node_hex[:12]}"
            handle = None
            try:
                # Adopt a live orphan first (e.g. a prior reconcile that
                # timed out after the actor booted) — the name is
                # unique, so re-creating would fail forever.
                try:
                    handle = ray_tpu.get_actor(name)
                except Exception:
                    handle = ray_tpu.remote(ProxyReplica).options(
                        name=name,
                        scheduling_strategy=NodeAffinitySchedulingStrategy(
                            node_id=node_hex, soft=False),
                    ).remote(self._proxy_config["host"],
                             self._proxy_config["port"])
                addr_ref = handle.address.remote()
                _node, h, p = await asyncio.wait_for(addr_ref, timeout=60)
                if h in ("0.0.0.0", "::") and node_host.get(node_hex):
                    h = node_host[node_hex]
                self._proxies[node_hex] = (handle, (h, p))
                self._proxy_errors.pop(node_hex, None)
            except Exception:
                # Node racing away / worker boot failure: kill the
                # half-created actor (a live orphan would hold the name
                # and wedge every future attempt), keep the last error
                # observable, retry next tick.
                if handle is not None:
                    try:
                        ray_tpu.kill(handle)
                    except Exception:
                        pass
                self._proxy_errors[node_hex] = traceback.format_exc()
                continue

    async def proxy_errors(self) -> Dict[str, str]:
        return dict(self._proxy_errors)

    async def _reconcile_loop(self):
        tick = 0
        while not self._shutdown:
            try:
                await self._reconcile_once()
                if tick % 4 == 1:
                    await self._health_and_autoscale()
                if tick % 8 == 2:
                    await self._reconcile_proxies()
            except Exception:
                pass
            tick += 1
            await asyncio.sleep(0.5)

    async def ping(self) -> bool:
        self._ensure_loop_task()
        return True


def get_controller():
    """Get-or-create the named controller actor (reference:
    serve/_private/api.py _get_global_client)."""
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        pass
    handle = ray_tpu.remote(ServeController).options(
        name=CONTROLLER_NAME, max_concurrency=1000).remote()
    ray_tpu.get(handle.ping.remote())
    return handle
