"""gRPC ingress proxy for Serve applications.

Ref analog: the reference's experimental gRPC ingress —
python/ray/serve/drivers.py (gRPCIngress) and
python/ray/serve/_private/grpc_util.py (RayServeAPIService wiring) —
re-designed without protoc codegen: the service is registered with
``grpc.method_handlers_generic_handler`` using identity (bytes)
serializers, so any gRPC client can call it by full method name with
JSON payloads.  Service surface:

  /ray.serve.ServeAPIService/Healthz           unary-unary
  /ray.serve.ServeAPIService/ListApplications  unary-unary
  /ray.serve.ServeAPIService/Predict           unary-unary
  /ray.serve.ServeAPIService/Streaming         unary-stream

Routing follows the reference's metadata convention: the target app is
the ``application`` entry in the call's invocation metadata, falling
back to the single deployed app when only one exists.  Request bytes
are JSON-decoded into the handle argument; responses are JSON bytes
(or raw bytes passthrough when the deployment returns ``bytes``).

Backpressure: ``maximum_concurrent_rpcs`` on the grpc server rejects
excess calls with RESOURCE_EXHAUSTED — the proxy-level saturation
semantics the HTTP proxy expresses with 503 + Retry-After.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import ray_tpu

GRPC_PROXY_NAME = "SERVE_GRPC_PROXY"
SERVICE_NAME = "ray.serve.ServeAPIService"
_ROUTES_TTL_S = 1.0
_REQUEST_TIMEOUT_S = 60.0


def _ident(b: bytes) -> bytes:
    return b


class GrpcProxy:
    """Actor hosting the gRPC server (one per cluster by default)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 max_concurrent_rpcs: Optional[int] = None,
                 workers: int = 16):
        import grpc

        self._controller = None
        self._apps: dict = {}
        self._apps_at = 0.0
        self._handles: dict = {}
        self._refresh_lock = threading.Lock()
        self._loaded = False  # one cold-start route fetch has completed
        # rejection must be prompt: each handler can block its executor
        # thread up to the request timeout, so the RPC cap is tied to
        # the thread count (workers running + workers queued) — not an
        # arbitrary large constant that would let calls 17..N sit in the
        # executor queue until DEADLINE_EXCEEDED instead of failing fast
        # with RESOURCE_EXHAUSTED
        if max_concurrent_rpcs is None:
            max_concurrent_rpcs = workers * 2
        self._server = grpc.server(
            ThreadPoolExecutor(max_workers=workers,
                               thread_name_prefix="serve-grpc"),
            maximum_concurrent_rpcs=max_concurrent_rpcs)
        handlers = {
            "Healthz": grpc.unary_unary_rpc_method_handler(
                self._healthz, _ident, _ident),
            "ListApplications": grpc.unary_unary_rpc_method_handler(
                self._list_apps, _ident, _ident),
            "Predict": grpc.unary_unary_rpc_method_handler(
                self._predict, _ident, _ident),
            "Streaming": grpc.unary_stream_rpc_method_handler(
                self._streaming, _ident, _ident),
        }
        self._server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE_NAME, handlers),))
        self._port = self._server.add_insecure_port(f"{host}:{port}")
        self._server.start()

    # ------------------------------------------------------------- handlers

    def _healthz(self, request: bytes, context) -> bytes:
        return b'{"status": "ok"}'

    def _list_apps(self, request: bytes, context) -> bytes:
        return json.dumps(sorted(self._app_table())).encode()

    def _predict(self, request: bytes, context) -> bytes:
        handle, arg = self._resolve(request, context)
        resp = handle.remote(arg)
        result = resp.result(timeout_s=_REQUEST_TIMEOUT_S)
        if isinstance(result, bytes):
            return result
        return json.dumps(result).encode()

    def _streaming(self, request: bytes, context):
        handle, arg = self._resolve(request, context)
        for item in handle.options(stream=True).remote(arg):
            yield (item if isinstance(item, bytes)
                   else json.dumps(item).encode())

    # -------------------------------------------------------------- routing

    def _resolve(self, request: bytes, context):
        import grpc

        md = dict(context.invocation_metadata() or ())
        apps = self._app_table()
        app = md.get("application")
        if app is None and len(apps) == 1:
            app = next(iter(apps))
        if app is None or app not in apps:
            context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"application {app!r} not found; deployed: {sorted(apps)}")
        arg = None
        if request:
            try:
                arg = json.loads(request)
            except json.JSONDecodeError:
                arg = request  # raw-bytes passthrough
        return self._app_handle(app), arg

    def _controller_handle(self):
        if self._controller is None:
            from .controller import CONTROLLER_NAME

            self._controller = ray_tpu.get_actor(CONTROLLER_NAME)
        return self._controller

    def _app_table(self) -> dict:
        """app name -> route prefix, with the same TTL/staleness policy
        as the HTTP proxy's route table. Refreshes are coalesced: one
        controller RPC per expiry no matter how many handler threads
        cross the TTL together (the HTTP proxy learned this the hard
        way — the per-request controller RPC dominated proxy latency)."""
        if time.monotonic() - self._apps_at > _ROUTES_TTL_S:
            # cold start (no completed load attempt) must BLOCK on the
            # lock: serving the initial empty table would turn a racing
            # first request into a spurious NOT_FOUND, which gRPC
            # clients don't retry. Afterwards, losers of the acquire
            # race serve the (possibly stale) table instead of stacking
            # up behind the RPC.
            if self._refresh_lock.acquire(blocking=not self._loaded):
                try:
                    if time.monotonic() - self._apps_at > _ROUTES_TTL_S:
                        routes = ray_tpu.get(
                            self._controller_handle().get_routes.remote(),
                            timeout=10)
                        old = self._apps
                        new = {app: prefix
                               for prefix, app in routes.items()}
                        # Invalidate ONLY handles whose app's route
                        # actually changed (redeploy/removal) — dropping
                        # the whole cache every 1s refresh made the next
                        # Predict per app pay a blocking get_ingress
                        # controller RPC every second under steady
                        # traffic.
                        self._handles = {
                            a: h for a, h in self._handles.items()
                            if a in new and new[a] == old.get(a)}
                        self._apps = new
                        self._apps_at = time.monotonic()
                except Exception:  # noqa: BLE001 — keep serving stale
                    pass
                finally:
                    # loaded marks "a cold-start attempt COMPLETED", not
                    # "it succeeded": if the controller is unreachable,
                    # later requests must fail fast on the empty table
                    # rather than serially repeating a 10s blocking RPC
                    # from every executor thread
                    self._loaded = True
                    self._refresh_lock.release()
        return self._apps

    def _app_handle(self, app: str):
        from .handle import DeploymentHandle

        handle = self._handles.get(app)
        if handle is None:
            ingress = ray_tpu.get(
                self._controller_handle().get_ingress.remote(app),
                timeout=10)
            handle = DeploymentHandle(ingress, app)
            self._handles[app] = handle
        return handle

    # -------------------------------------------------------------- public

    def port(self) -> int:
        return self._port

    def ready(self) -> bool:
        return True

    def stop(self):
        self._server.stop(grace=1.0)
        return True


def start_grpc(host: str = "127.0.0.1", port: int = 0) -> int:
    """Start the gRPC ingress (idempotent); returns the bound port.

    Like ``serve.start()``, host/port apply only on first start: if the
    proxy actor already exists its existing binding is returned (call
    ``stop_grpc()`` first to rebind)."""
    from .api import get_or_create_controller

    get_or_create_controller()
    try:
        proxy = ray_tpu.get_actor(GRPC_PROXY_NAME)
    except ValueError:
        proxy = ray_tpu.remote(GrpcProxy).options(
            name=GRPC_PROXY_NAME, num_cpus=0, max_concurrency=32).remote(
                host, port)
    return ray_tpu.get(proxy.port.remote(), timeout=30)


def stop_grpc():
    try:
        proxy = ray_tpu.get_actor(GRPC_PROXY_NAME)
    except ValueError:
        return
    try:
        ray_tpu.get(proxy.stop.remote(), timeout=10)
    except Exception:  # noqa: BLE001
        pass
    ray_tpu.kill(proxy)
