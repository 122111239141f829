"""Replica actor: hosts one copy of a deployment's user callable.

Reference: python/ray/serve/_private/replica.py — the replica actor
receives requests pushed by routers, tracks ongoing-request count (the
router's power-of-two signal), runs health checks and reconfigure.

TPU note: a replica is where a `jax.jit` model lives; the actor's
`ray_actor_options` reserve TPU chips so the scheduler gives each replica
exclusive chips, and requests run through serve.batch batching so XLA
compiles a handful of bucket shapes once.
"""
import asyncio
import inspect
import time
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu._private import telemetry
from ray_tpu.util.profiling import annotate


class StreamingResponseRequired(Exception):
    """The handler returned a generator on the unary call path; the
    caller must retry via handle_request_streaming."""


class VerdictMismatch(Exception):
    """The proxy trimmed the request per its learned ASGI/classic
    verdict, but this replica's handler is the OTHER kind (a same-name
    redeploy swapped the deployment type). Raised BEFORE user code runs,
    so the proxy can safely retry with the full request."""

    # The proxy sees remote errors as flattened TaskError text, so it
    # matches this token rather than the class name — a user exception
    # merely MENTIONING "VerdictMismatch" must not trigger a retry
    # (requests may be non-idempotent).
    TOKEN = "__ray_tpu_verdict_mismatch__"

    def __init__(self, deployment_name: str):
        super().__init__(f"{self.TOKEN} {deployment_name}")


def _check_trim(req, callable_obj, deployment_name: str) -> None:
    """Pop the proxy's __trim__ marker and refuse (before user code
    runs) if the learned verdict no longer matches this handler's
    kind."""
    if isinstance(req, dict) and "__trim__" in req:
        trim = req.pop("__trim__")
        handler_is_asgi = hasattr(callable_obj, "__serve_asgi_app__")
        if (trim == "asgi") != handler_is_asgi:
            raise VerdictMismatch(deployment_name)


class Replica:
    """User-code host (reference: replica.py UserCallableWrapper)."""

    def __init__(self, cls_blob: bytes, init_args: tuple,
                 init_kwargs: dict, deployment_name: str,
                 user_config: Optional[Any] = None):
        import cloudpickle
        target = cloudpickle.loads(cls_blob)
        self._deployment_name = deployment_name
        self._ongoing = 0
        if inspect.isclass(target):
            self._callable = target(*init_args, **init_kwargs)
        else:
            # Function deployment: the function IS the request handler.
            self._callable = target
        if user_config is not None:
            self._apply_user_config(user_config)

    def _apply_user_config(self, user_config):
        fn = getattr(self._callable, "reconfigure", None)
        if fn is None:
            raise ValueError(
                f"Deployment {self._deployment_name} passed user_config but "
                "its class defines no reconfigure(user_config) method")
        fn(user_config)

    async def handle_request(self, method_name: str, args: tuple,
                             kwargs: dict,
                             multiplexed_model_id: str = "") -> Any:
        """Run one request through the user callable.

        Sync user code is offloaded to a thread so the replica's event loop
        keeps serving concurrent requests (reference fibers/asyncio model:
        replica.py + transport/fiber.h).
        """
        from ..multiplex import _set_request_model_id
        self._ongoing += 1
        t0 = None
        if telemetry.enabled:
            # Replica-side dispatch metrics: these live in the worker
            # process's registry and reach the head via the piggybacked
            # METRICS_PUSH (telemetry.py metric federation).
            t0 = time.monotonic()
            telemetry.serve_replica_ongoing(self._deployment_name,
                                            self._ongoing)
        _set_request_model_id(multiplexed_model_id)
        try:
            # Proxy HTTP requests carry a __trim__ marker when a learned
            # verdict dropped one half of the request payload. If the
            # verdict no longer matches this replica's handler kind (a
            # same-name redeploy swapped ASGI <-> classic), refuse
            # BEFORE running user code: the proxy drops its verdict and
            # retries once with the full request — no side effects run
            # twice and no stale-verdict 500 loop forms.
            if args:
                _check_trim(args[0], self._callable,
                            self._deployment_name)
            if inspect.isfunction(self._callable) or inspect.ismethod(
                    self._callable) or not hasattr(
                        self._callable, method_name):
                target = self._callable  # function deployment
            else:
                target = getattr(self._callable, method_name)
            if inspect.isgeneratorfunction(target) or \
                    inspect.isasyncgenfunction(target):
                # Statically streaming: refuse BEFORE executing so the
                # streaming retry doesn't double-run side effects.
                raise StreamingResponseRequired(self._deployment_name)
            if inspect.iscoroutinefunction(target):
                with annotate("ray_tpu.serve.handle"):
                    result = await target(*args, **kwargs)
            else:
                import contextvars
                # ctx.run: the executor thread must see the request's
                # multiplexed model id (run_in_executor does not
                # propagate contextvars by itself).
                ctx = contextvars.copy_context()

                def in_handler_thread():
                    if t0 is not None:
                        # What the executor's thread count (asyncio's
                        # default: min(32, cores + 4)) makes a request
                        # wait once that many handlers run.
                        telemetry.serve_replica_handler_wait(  # lint: ungated-instrumentation-ok t0 is non-None only when telemetry.enabled was set at entry
                            self._deployment_name, time.monotonic() - t0)
                    with annotate("ray_tpu.serve.handle"):
                        return ctx.run(target, *args, **kwargs)
                result = await asyncio.get_event_loop().run_in_executor(
                    None, in_handler_thread)
            if inspect.isgenerator(result) or inspect.isasyncgen(result):
                # Caller used the non-streaming path on a handler that
                # DYNAMICALLY returned a generator; tell it to retry via
                # handle_request_streaming (the proxy caches the verdict
                # per deployment). KNOWN LIMITATION: the handler body has
                # already run once here, so side effects execute twice
                # for this one transition request — same as the
                # reference's requirement that streaming handlers be
                # declared, minus the declaration. Statically detectable
                # generators are refused before execution above.
                raise StreamingResponseRequired(self._deployment_name)
            return result
        finally:
            self._ongoing -= 1
            if t0 is not None:
                telemetry.serve_replica_request(self._deployment_name,  # lint: ungated-instrumentation-ok t0 is non-None only when telemetry.enabled was set at entry
                                                time.monotonic() - t0)
                telemetry.serve_replica_ongoing(self._deployment_name,  # lint: ungated-instrumentation-ok t0 gate, as above
                                                self._ongoing)

    def _resolve_target(self, method_name: str):
        if inspect.isfunction(self._callable) or inspect.ismethod(
                self._callable) or not hasattr(self._callable,
                                               method_name):
            return self._callable  # function deployment
        return getattr(self._callable, method_name)

    def handle_request_streaming(self, method_name: str, args: tuple,
                                 kwargs: dict,
                                 multiplexed_model_id: str = ""):
        """Generator variant of handle_request (reference: streaming
        responses through the proxy, serve/_private/replica.py
        call_user_generator). First yielded item is a marker dict so the
        consumer knows whether the user returned a stream or one value;
        user generators then stream item by item over GEN_ITEM messages.
        """
        import contextvars

        from ..multiplex import _set_request_model_id
        self._ongoing += 1
        # Per-REQUEST context: two interleaved streaming requests share
        # this thread, so the model id must live in a context copied
        # for this generator — user code calling
        # serve.get_multiplexed_model_id() after the first yield must
        # never read the OTHER request's id.
        req_ctx = contextvars.copy_context()
        try:
            def _start():
                _set_request_model_id(multiplexed_model_id)
                # Same mismatch refusal as the unary path: a stream-mode
                # deployment swapped to the other kind by a same-name
                # redeploy must not silently run on a trimmed request.
                if args:
                    _check_trim(args[0], self._callable,
                                self._deployment_name)
                target = self._resolve_target(method_name)
                with annotate("ray_tpu.serve.handle"):
                    result = target(*args, **kwargs)
                    if inspect.iscoroutine(result):
                        result = asyncio.run(result)
                return result

            result = req_ctx.run(_start)
            if inspect.isgenerator(result):
                yield {"__stream__": True}
                try:
                    while True:
                        try:
                            item = req_ctx.run(next, result)
                        except StopIteration:
                            break
                        yield item
                finally:
                    # An abandoned stream (consumer close ->
                    # GeneratorExit at the yield above) must close the
                    # USER generator now so its finally/context-manager
                    # cleanup runs deterministically, as `yield from`
                    # would have done.
                    try:
                        req_ctx.run(result.close)
                    except Exception:
                        pass
            else:
                yield {"__stream__": False}
                yield result
        finally:
            self._ongoing -= 1

    async def get_queue_len(self) -> int:
        """Power-of-two probe (reference: replica scheduler queue-length
        probes, pow_2_scheduler.py:52)."""
        return self._ongoing

    async def get_queue_len_and_models(self) -> tuple:
        """Combined probe: (queue length, multiplexed model ids loaded
        here). Routers use the ids for model-aware routing (reference:
        pow_2_scheduler's multiplexed ranking via controller-pushed
        model ids — here the info rides the existing probe instead)."""
        from ..multiplex import loaded_model_ids
        return self._ongoing, loaded_model_ids(self._callable)

    async def reconfigure(self, user_config) -> bool:
        self._apply_user_config(user_config)
        return True

    async def check_health(self) -> bool:
        fn = getattr(self._callable, "check_health", None)
        if fn is not None:
            out = fn()
            if inspect.isawaitable(out):
                out = await out
            return bool(out) if out is not None else True
        return True

    async def prepare_shutdown(self) -> bool:
        fn = getattr(self._callable, "__del__", None)
        return True


def start_replica(deployment_name: str, replica_idx: int, cls_blob: bytes,
                  init_args: tuple, init_kwargs: dict,
                  actor_options: Dict[str, Any],
                  max_ongoing_requests: int,
                  user_config: Optional[Any] = None):
    """Spawn one replica actor (reference: deployment_state.py
    _start_replica)."""
    opts = dict(actor_options)
    opts.setdefault("name", f"SERVE_REPLICA::{deployment_name}#{replica_idx}")
    opts["max_concurrency"] = max(int(max_ongoing_requests) * 2, 16)
    return ray_tpu.remote(Replica).options(**opts).remote(
        cls_blob, init_args, init_kwargs, deployment_name, user_config)
