"""gRPC bytes transport.

The reference builds protobuf-codegen services with unlimited message sizes
(reference metisfl/utils/grpc_services.py:22-110). Here services are generic
byte methods (no codegen): each endpoint is a named unary handler taking and
returning codec/blob bytes. Retry-with-backoff on UNAVAILABLE mirrors
grpc_services.py:60-75; unlimited message lengths mirror :28-30 and :93-97.

Chunked transfer (SURVEY.md §7 hard parts): "unlimited" gRPC message sizes
still stop at protobuf's ~2 GiB per-message framing, and the reference
already collapsed well before that — its controller opens a fresh
channel+stub per request to dodge a throughput cliff at ~100 MB FHE models
(FIXME, reference metisfl/controller/core/controller.cc:594-604). Here
every unary method transparently doubles as a chunked stream-stream method:
payloads above ``STREAM_THRESHOLD`` are framed into ``CHUNK_BYTES``
segments and reassembled server-side (and response-side), so a >2 GiB
model blob round-trips through the same ``call()`` API. A unary response
that would exceed framing is refused server-side with RESOURCE_EXHAUSTED
and the client transparently retries over the chunked path.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from concurrent import futures
from typing import Callable, Dict, Optional

import grpc

from metisfl_tpu import chaos as _chaos
from metisfl_tpu.comm.codec import Segments
from metisfl_tpu.telemetry import events as _events
from metisfl_tpu import telemetry as _tel
from metisfl_tpu.telemetry import metrics as _metrics
from metisfl_tpu.telemetry import trace as _trace

logger = logging.getLogger("metisfl_tpu.rpc")

# Default per-call deadline when the caller passes timeout=None. An
# unbounded RPC means one hung peer can park a dispatch thread forever
# (SURVEY.md §5.3 is full of exactly that failure); every call gets a
# bound unless the caller explicitly opts out (timeout <= 0 via
# CommConfig.default_deadline_s <= 0). Sized for cold-jit learners and
# multi-GB chunked model transfers, not for acks.
DEFAULT_DEADLINE_S = 120.0

# Per-method RPC metrics (telemetry registry; families are idempotent so
# module reload is safe). Client counters are LOGICAL: one sample per
# call() regardless of transparent retries — the retried label says
# whether any fail-then-retry (UNAVAILABLE backoff or unary-oversize →
# chunked) happened inside. Server counters are per handler invocation,
# so the oversize path visibly costs two invocations for one call.
_REG = _metrics.registry()
_M_CLIENT_CALLS = _REG.counter(
    _tel.M_RPC_CLIENT_CALLS_TOTAL, "Logical client calls (retries collapsed)",
    ("service", "method", "retried"))
_M_CLIENT_LATENCY = _REG.histogram(
    _tel.M_RPC_CLIENT_LATENCY_SECONDS, "Logical client call latency",
    ("service", "method"))
_M_CLIENT_BYTES = _REG.counter(
    _tel.M_RPC_CLIENT_BYTES_TOTAL, "Client payload bytes by direction",
    ("service", "method", "direction"))
_M_CLIENT_ERRORS = _REG.counter(
    _tel.M_RPC_CLIENT_ERRORS_TOTAL, "Client calls that raised after retries",
    ("service", "method", "code"))
_M_SERVER_CALLS = _REG.counter(
    _tel.M_RPC_SERVER_CALLS_TOTAL, "Handler invocations",
    ("service", "method", "transport"))
_M_SERVER_LATENCY = _REG.histogram(
    _tel.M_RPC_SERVER_LATENCY_SECONDS, "Server handler latency",
    ("service", "method"))
_M_SERVER_BYTES = _REG.counter(
    _tel.M_RPC_SERVER_BYTES_TOTAL, "Server payload bytes by direction",
    ("service", "method", "direction"))
_M_SERVER_ERRORS = _REG.counter(
    _tel.M_RPC_SERVER_ERRORS_TOTAL, "Handler invocations that raised",
    ("service", "method"))
# Per-peer wire bytes (performance observatory): a client constructed
# with ``peer=<learner_id>`` additionally attributes its payload bytes —
# envelopes included, unlike the controller's payload-level
# uplink/downlink counters — to that peer. Series are pruned on learner
# leave via ``prune_peer_series`` (bounded cardinality under churn).
_M_PEER_BYTES = _REG.counter(
    _tel.M_RPC_PEER_BYTES_TOTAL,
    "Client payload bytes attributed to one peer (learner id), by "
    "direction", ("peer", "direction"), budget_label="peer")


def prune_peer_series(peer: str) -> None:
    for direction in ("sent", "received"):
        _M_PEER_BYTES.remove(peer=peer, direction=direction)


def _error_code_name(exc: Exception) -> str:
    code = exc.code() if hasattr(exc, "code") else None
    return code.name if isinstance(code, grpc.StatusCode) else "UNKNOWN"

_UNLIMITED = [
    ("grpc.max_send_message_length", -1),
    ("grpc.max_receive_message_length", -1),
    # gRPC servers default to SO_REUSEPORT on Linux: two federations (or a
    # stale controller from a crashed run) binding the same port would
    # silently load-balance RPCs between unrelated processes. Fail loudly.
    ("grpc.so_reuseport", 0),
]

_IDENTITY = lambda b: b  # noqa: E731 - bytes in, bytes out

# Chunked-transfer framing. CHUNK_BYTES balances per-message overhead
# against flow-control pipelining; STREAM_THRESHOLD stays far under both
# protobuf's ~2 GiB hard framing limit and the reference's observed
# ~100 MB reused-channel throughput cliff. Module-level so tests (and
# operators) can tune them.
CHUNK_BYTES = 32 * 1024 * 1024
STREAM_THRESHOLD = 128 * 1024 * 1024
# a unary RESPONSE above this cannot be framed — refuse server-side and
# let the client retry chunked (margin under the 2 GiB wire limit)
UNARY_RESPONSE_LIMIT = (2 << 30) - (64 << 20)
_CHUNK_SUFFIX = "Chunked"
_OVERSIZE_MARK = "response exceeds unary framing; retry chunked"


def _as_bytes(payload) -> bytes:
    """A payload is ``bytes`` or a codec ``Segments``; what takes one
    message whole (a unary call, a chaos injector) gets it joined."""
    return bytes(payload) if isinstance(payload, Segments) else payload


def _iter_chunks(payload):
    """Frames of ``CHUNK_BYTES`` (the last one shorter) over a payload's
    bytes, filled across segment borders: the one copy gRPC needs, and
    the same frames whether the payload came joined or in segments."""
    if not len(payload):
        yield b""
        return
    parts = payload.parts if isinstance(payload, Segments) else (payload,)
    frame, room = [], CHUNK_BYTES
    for part in parts:
        view = memoryview(part)
        while len(view):
            piece, view = view[:room], view[room:]
            frame.append(piece)
            room -= len(piece)
            if not room:
                yield b"".join(frame)
                frame, room = [], CHUNK_BYTES
    if frame:
        yield b"".join(frame)


class BytesService:
    """A named set of unary bytes→bytes methods served over gRPC.

    Every service automatically answers ``ListMethods`` (the reference's
    gRPC-reflection role): the dispatch table's method names plus the
    transport capability flags — every method doubles as a chunked
    stream, and oversize unary responses fall back to it. The reply is
    JSON (not the wire codec) so generic tooling — the status CLI's
    endpoint probe, a curl through grpcurl — can read it without this
    package.

    Handler contract: a handler whose response can exceed
    :data:`UNARY_RESPONSE_LIMIT` MUST be idempotent — the oversize
    fallback refuses the unary response after the handler already ran
    and the client transparently re-invokes it over the chunked method,
    so such a handler executes twice per logical call (fine for getters
    like GetCommunityModel; a non-idempotent method must keep its
    responses under the limit or route clients to chunked up front).
    """

    def __init__(self, service_name: str,
                 handlers: Dict[str, Callable[[bytes], bytes]],
                 role: str = ""):
        self.service_name = service_name
        # endpoint role ("controller" | "learner" | "serving" | ...): the
        # status CLI's --probe tells a serving gateway apart from a
        # learner without guessing from method names
        self.role = role
        self.handlers = dict(handlers)
        self.handlers.setdefault("ListMethods", self._list_methods)
        if role:
            # fleet telemetry fabric (telemetry/fabric.py): every
            # role-carrying endpoint answers cursor-based telemetry
            # pulls next to ListMethods/GetMetrics — event tail,
            # finished-span ring, metrics state, and the continuous-
            # profiling section (telemetry/prof.py folded stacks + lock
            # contention). With telemetry.fabric.enabled=false the
            # handler answers a one-attribute-check {"enabled": false}
            # stub (telemetry.prof.enabled=false stubs just its
            # section).
            self.handlers.setdefault("CollectTelemetry",
                                     self._collect_telemetry)

    def _collect_telemetry(self, raw: bytes) -> bytes:
        from metisfl_tpu.telemetry import fabric as _fabric
        return _fabric.handle_collect(raw, self.service_name, self.role)

    def _list_methods(self, raw: bytes) -> bytes:
        methods = [
            {"name": name, "transports": ["unary", "chunked"],
             "oversize_unary_fallback": True}
            for name in sorted(self.handlers)
        ]
        reply = {"service": self.service_name, "methods": methods}
        if self.role:
            reply["role"] = self.role
        return json.dumps(reply).encode("utf-8")

    def _generic_handler(self) -> grpc.GenericRpcHandler:
        method_handlers = {}
        for name, fn in self.handlers.items():
            method_handlers[name] = grpc.unary_unary_rpc_method_handler(
                self._wrap(name, fn),
                request_deserializer=_IDENTITY,
                response_serializer=_IDENTITY,
            )
            # every method transparently doubles as a chunked stream:
            # RpcClient routes payloads above STREAM_THRESHOLD (and
            # oversize-response retries) here
            method_handlers[name + _CHUNK_SUFFIX] = \
                grpc.stream_stream_rpc_method_handler(
                    self._wrap_chunked(name, fn),
                    request_deserializer=_IDENTITY,
                    response_serializer=_IDENTITY,
                )
        return grpc.method_handlers_generic_handler(
            self.service_name, method_handlers)

    @staticmethod
    def _abort(context: grpc.ServicerContext, exc: Exception):
        code = getattr(exc, "code", None)
        if callable(code):  # RpcError-shaped (incl. chaos FaultInjected)
            try:
                code = code()
            except Exception:  # noqa: BLE001 - fall through to INTERNAL
                code = None
        if isinstance(code, grpc.StatusCode):
            context.abort(code, str(exc))
        if isinstance(exc, ValueError):
            # malformed input (codec framing, blob integrity/checksum) is
            # the caller's defect, not a server bug: reject it as
            # INVALID_ARGUMENT so clients/retry ladders never treat a
            # corrupt payload as a transient server failure
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          f"{type(exc).__name__}: {exc}")
        logger.exception("RPC handler failed")
        context.abort(grpc.StatusCode.INTERNAL,
                      f"{type(exc).__name__}: {exc}")

    def _wrap(self, method: str, fn: Callable[[bytes], bytes]):
        service = self.service_name

        def handler(request: bytes, context: grpc.ServicerContext) -> bytes:
            t0 = time.perf_counter()
            _M_SERVER_CALLS.inc(service=service, method=method,
                                transport="unary")
            _M_SERVER_BYTES.inc(len(request), service=service,
                                method=method, direction="in")
            sp = _trace.span(
                f"rpc.server/{method}",
                parent=_trace.extract(context.invocation_metadata()),
                attrs={"service": service})
            try:
                with sp, sp.activate():
                    try:
                        inj = _chaos.get()
                        if inj is not None:
                            request = inj.intercept("server", service,
                                                    method, request)
                        result = fn(request)
                    except Exception as exc:
                        _M_SERVER_ERRORS.inc(service=service, method=method)
                        sp.set_attr("error", f"{type(exc).__name__}: {exc}")
                        BytesService._abort(context, exc)
                if len(result) > UNARY_RESPONSE_LIMIT:
                    # cannot frame this as one message — the client retries
                    # over the chunked method on this exact status+detail.
                    # NOTE the handler has already run to completion here
                    # and will run AGAIN on the retry: only idempotent
                    # handlers may return oversize responses (see the
                    # BytesService class docstring).
                    context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                  _OVERSIZE_MARK)
                _M_SERVER_BYTES.inc(len(result), service=service,
                                    method=method, direction="out")
                return result
            finally:
                _M_SERVER_LATENCY.observe(time.perf_counter() - t0,
                                          service=service, method=method)

        return handler

    def _wrap_chunked(self, method: str, fn: Callable[[bytes], bytes]):
        service = self.service_name

        def handler(request_iter, context: grpc.ServicerContext):
            t0 = time.perf_counter()
            _M_SERVER_CALLS.inc(service=service, method=method,
                                transport="chunked")
            try:
                try:
                    # draining the request stream can itself fail (client
                    # cancelled mid-upload): shape it like a handler error
                    # so metrics and status stay consistent
                    request = b"".join(request_iter)
                except Exception as exc:
                    _M_SERVER_ERRORS.inc(service=service, method=method)
                    BytesService._abort(context, exc)
                _M_SERVER_BYTES.inc(len(request), service=service,
                                    method=method, direction="in")
                sp = _trace.span(
                    f"rpc.server/{method}",
                    parent=_trace.extract(context.invocation_metadata()),
                    attrs={"service": service, "transport": "chunked"})
                with sp, sp.activate():
                    try:
                        inj = _chaos.get()
                        if inj is not None:
                            request = inj.intercept("server", service,
                                                    method, request)
                        result = fn(request)
                    except Exception as exc:
                        _M_SERVER_ERRORS.inc(service=service, method=method)
                        sp.set_attr("error", f"{type(exc).__name__}: {exc}")
                        BytesService._abort(context, exc)
                _M_SERVER_BYTES.inc(len(result), service=service,
                                    method=method, direction="out")
            finally:
                _M_SERVER_LATENCY.observe(time.perf_counter() - t0,
                                          service=service, method=method)
            yield from _iter_chunks(result)

        return handler


class RpcServer:
    """gRPC server hosting one or more :class:`BytesService`s.

    ``ssl``: an enabled :class:`metisfl_tpu.comm.ssl.SSLConfig` serves TLS
    (reference controller_servicer.cc:38-74); None serves plaintext.
    """

    def __init__(self, host: str, port: int, max_workers: int = 16, ssl=None):
        self.host = host
        self.port = port
        self.ssl = ssl if (ssl is not None and ssl.enabled) else None
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=_UNLIMITED,
        )
        self._bound_port: Optional[int] = None

    def add_service(self, service: BytesService) -> None:
        self._server.add_generic_rpc_handlers((service._generic_handler(),))

    def start(self) -> int:
        addr = f"{self.host}:{self.port}"
        if self.ssl is not None:
            from metisfl_tpu.comm.ssl import server_credentials
            self._bound_port = self._server.add_secure_port(
                addr, server_credentials(self.ssl))
        else:
            self._bound_port = self._server.add_insecure_port(addr)
        if self._bound_port == 0:
            raise RuntimeError(f"could not bind gRPC server on {addr}")
        self._server.start()
        logger.info("gRPC server listening on %s:%d%s", self.host,
                    self._bound_port, " (TLS)" if self.ssl else "")
        return self._bound_port

    def stop(self, grace: float = 1.0) -> None:
        self._server.stop(grace).wait()

    def wait(self) -> None:
        self._server.wait_for_termination()


class StopOnce:
    """Stop-once + wait-until-stopped lifecycle of a process's server.

    ``wait_for_shutdown`` returns when teardown has FINISHED, not when it
    began. An entry point's main thread exits on it, and interpreter
    finalization flips ``concurrent.futures``' global shutdown flag: an
    RPC that arrives while a ShutDown-RPC daemon thread is still tearing
    the server down then kills gRPC's serve thread with "cannot schedule
    new futures after shutdown", the server never finishes stopping, and
    the process hangs until it is SIGKILLed — holding its chip. (Seen on
    the first four-learner TPU run, where teardown waits on an 805 MB
    task in flight.) Subclasses implement ``_teardown``."""

    def __init__(self):
        self._stop_lock = threading.Lock()
        self._stopping = False
        self._stopped = threading.Event()

    def stop(self, **kwargs) -> None:
        with self._stop_lock:
            if self._stopping:
                return
            self._stopping = True
        try:
            self._teardown(**kwargs)
        finally:
            self._stopped.set()

    def wait_for_shutdown(self, timeout: Optional[float] = None) -> bool:
        return self._stopped.wait(timeout)


class RpcClient:
    """Channel to a :class:`BytesService` with retry/backoff on UNAVAILABLE.

    ``default_deadline_s``: deadline applied when a call passes
    ``timeout=None`` (config ``comm.default_deadline_s``). ``None`` →
    :data:`DEFAULT_DEADLINE_S`; ``<= 0`` → explicitly unbounded (the old
    behavior, for operators who really want it).
    """

    def __init__(self, host: str, port: int, service_name: str,
                 retries: int = 10, retry_sleep_s: float = 1.0, ssl=None,
                 default_deadline_s: Optional[float] = None,
                 peer: str = ""):
        self.target = f"{host}:{port}"
        self.service_name = service_name
        # optional peer identity (a learner id): when set, payload bytes
        # additionally land in the peer-labeled wire-byte counter (the
        # performance observatory's per-learner wire attribution)
        self.peer = peer
        self.retries = retries
        self.retry_sleep_s = retry_sleep_s
        if default_deadline_s is None:
            default_deadline_s = DEFAULT_DEADLINE_S
        self.default_deadline_s = (default_deadline_s
                                   if default_deadline_s > 0 else None)
        if ssl is not None and ssl.enabled:
            from metisfl_tpu.comm.ssl import channel_credentials
            self._channel = grpc.secure_channel(
                self.target, channel_credentials(ssl), options=_UNLIMITED)
        else:
            self._channel = grpc.insecure_channel(self.target, options=_UNLIMITED)
        # eager (threads only spawn on first submit): lazy init would race
        # between the app thread and grpc callback threads
        self._stream_pool = futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="rpc-chunked")
        # methods observed to need chunked responses: remember so later
        # calls skip the fail-then-retry (which runs the handler twice)
        self._chunked_methods: set = set()

    def call(self, method: str, payload, timeout: Optional[float] = None,
             wait_ready: bool = True, idempotent: bool = False) -> bytes:
        """``payload``: ``bytes``, or a codec ``Segments`` (the chunked
        stream gathers it frame by frame; the unary path joins it once).

        ``idempotent=True`` additionally retries DEADLINE_EXCEEDED —
        only safe for methods whose re-execution cannot double-apply
        (getters, join/rejoin, health)."""
        if timeout is None:
            timeout = self.default_deadline_s
        chunked = (len(payload) > STREAM_THRESHOLD
                   or method in self._chunked_methods)
        if not chunked:
            payload = _as_bytes(payload)
        attempt = 0
        retried = 0
        t0 = time.perf_counter()
        try:
            while True:
                try:
                    inj = _chaos.get()
                    send = (payload if inj is None else inj.intercept(
                        "client", self.service_name, method,
                        _as_bytes(payload)))
                    if chunked:
                        result = self._call_chunked(method, send, timeout,
                                                    wait_ready)
                    else:
                        fn = self._channel.unary_unary(
                            f"/{self.service_name}/{method}",
                            request_serializer=_IDENTITY,
                            response_deserializer=_IDENTITY,
                        )
                        result = fn(send, timeout=timeout,
                                    wait_for_ready=wait_ready,
                                    metadata=_trace.outbound_metadata())
                    self._count_bytes(len(payload), "sent", method=method)
                    self._count_bytes(len(result), "received", method=method)
                    return result
                except (grpc.RpcError, _chaos.FaultInjected) as exc:
                    code = exc.code() if hasattr(exc, "code") else None
                    if (not chunked
                            and code == grpc.StatusCode.RESOURCE_EXHAUSTED
                            and _OVERSIZE_MARK in (exc.details() or "")):
                        # the handler's response exceeds unary framing (e.g. a
                        # >2 GiB community model behind a tiny request):
                        # transparently re-issue over the chunked stream, and
                        # remember — the fail-then-retry runs the handler twice
                        chunked = True
                        retried = 1
                        self._chunked_methods.add(method)
                        _events.emit(_events.RetryScheduled,
                                     service=self.service_name,
                                     method=method, code="OVERSIZE_UNARY")
                        continue
                    retryable = (code == grpc.StatusCode.UNAVAILABLE
                                 or (idempotent and code
                                     == grpc.StatusCode.DEADLINE_EXCEEDED))
                    if retryable and attempt < self.retries:
                        attempt += 1
                        retried = 1
                        logger.warning("%s/%s %s (attempt %d/%d)",
                                       self.target, method,
                                       code.name.lower(), attempt,
                                       self.retries)
                        _events.emit(_events.RetryScheduled,
                                     service=self.service_name,
                                     method=method, code=code.name,
                                     attempt=attempt)
                        time.sleep(self.retry_sleep_s)
                        continue
                    _M_CLIENT_ERRORS.inc(service=self.service_name,
                                         method=method,
                                         code=_error_code_name(exc))
                    raise
        finally:
            # ONE logical-call sample however many transparent retries ran
            # inside (the regression contract tests/test_rpc.py pins)
            self._record_client_call(method, str(retried), t0)

    def _call_chunked(self, method: str, payload,
                      timeout: Optional[float], wait_ready: bool) -> bytes:
        fn = self._channel.stream_stream(
            f"/{self.service_name}/{method}{_CHUNK_SUFFIX}",
            request_serializer=_IDENTITY,
            response_deserializer=_IDENTITY,
        )
        return b"".join(fn(_iter_chunks(payload), timeout=timeout,
                           wait_for_ready=wait_ready,
                           metadata=_trace.outbound_metadata()))

    @staticmethod
    def _resolve(outer: "futures.Future", result=None,
                 exc: Optional[Exception] = None) -> None:
        """Resolve the caller-facing wrapper future, tolerating a caller
        that cancelled it while the call was in flight."""
        try:
            if exc is not None:
                outer.set_exception(exc)
            else:
                outer.set_result(result)
        except futures.InvalidStateError:  # pragma: no cover - cancelled
            pass

    def call_async(self, method: str, payload,
                   callback: Optional[Callable[[bytes], None]] = None,
                   error_callback: Optional[Callable[[Exception], None]] = None,
                   timeout: Optional[float] = None,
                   wait_ready: bool = True) -> "futures.Future":
        """Non-blocking unary call (the reference's CompletionQueue pattern,
        controller.cc:713-759, via grpc futures). ``wait_ready=False`` fails
        fast with UNAVAILABLE on a dead endpoint instead of queueing.
        Payloads above STREAM_THRESHOLD (and oversize unary responses)
        route through the chunked stream on a worker thread — stream
        draining has no grpc-future form.

        Returns a wrapper :class:`concurrent.futures.Future` resolved
        only by the FINAL outcome: a unary attempt refused oversize
        retries transparently over the chunked stream, and the wrapper
        stays pending until that retry settles — the caller never sees a
        failure for a call that then succeeds (a double signal).
        Callbacks fire exactly once either way."""
        # capture the span context HERE, on the caller's thread: grpc
        # completion callbacks and the stream pool run in their own
        # (empty) contextvars contexts, so an oversize retry issued from
        # _done would otherwise lose the trace parent
        ctx = _trace.current_context()
        t0 = time.perf_counter()
        outer: "futures.Future" = futures.Future()
        if timeout is None:
            timeout = self.default_deadline_s
        inj = _chaos.get()
        if inj is not None:
            # chaos fires synchronously on the caller's thread: a drop
            # raises here, which dispatch paths already treat as a failed
            # dispatch (liveness accounting)
            payload = inj.intercept("client", self.service_name, method,
                                    _as_bytes(payload))
        if (len(payload) > STREAM_THRESHOLD
                or method in self._chunked_methods):
            self._async_chunked(method, payload, callback,
                                error_callback, timeout, wait_ready,
                                ctx=ctx, t0=t0, outer=outer)
            return outer
        payload = _as_bytes(payload)
        fn = self._channel.unary_unary(
            f"/{self.service_name}/{method}",
            request_serializer=_IDENTITY,
            response_deserializer=_IDENTITY,
        )
        future = fn.future(payload, timeout=timeout, wait_for_ready=wait_ready,
                           metadata=_trace.outbound_metadata())

        def _done(f):
            try:
                result = f.result()
            except Exception as exc:  # noqa: BLE001 - surfaced via callback
                if (isinstance(exc, grpc.RpcError)
                        and exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
                        and _OVERSIZE_MARK in (exc.details() or "")):
                    self._chunked_methods.add(method)
                    _events.emit(_events.RetryScheduled,
                                 service=self.service_name,
                                 method=method, code="OVERSIZE_UNARY")
                    # still ONE logical call — the chunked leg records it
                    # (with retried="1"), not this failed unary attempt;
                    # the wrapper future resolves only with ITS outcome
                    self._async_chunked(method, payload, callback,
                                        error_callback, timeout, wait_ready,
                                        retried="1", ctx=ctx, t0=t0,
                                        outer=outer)
                    return
                # never invisible: count the failure whether or not the
                # caller asked to hear about it — and keep the logical-call
                # denominator honest (errors_total/calls_total <= 1)
                _M_CLIENT_ERRORS.inc(service=self.service_name,
                                     method=method,
                                     code=_error_code_name(exc))
                self._record_client_call(method, "0", t0)
                self._resolve(outer, exc=exc)
                if error_callback is not None:
                    error_callback(exc)
                else:
                    logger.warning("async RPC %s failed with no "
                                   "error_callback: %s", method, exc)
                return
            self._record_client_call(method, "0", t0, sent=len(payload),
                                     received=len(result))
            self._resolve(outer, result=result)
            if callback is not None:
                callback(result)

        future.add_done_callback(_done)
        return outer

    def _count_bytes(self, nbytes: int, direction: str,
                     method: str = "") -> None:
        """Payload bytes by direction: the per-method client counter, plus
        the peer-labeled series when this client is pinned to a peer."""
        if method:
            _M_CLIENT_BYTES.inc(nbytes, service=self.service_name,
                                method=method, direction=direction)
        if self.peer:
            _M_PEER_BYTES.inc(nbytes, peer=self.peer, direction=direction)

    def _record_client_call(self, method: str, retried: str, t0: float,
                            sent: Optional[int] = None,
                            received: Optional[int] = None) -> None:
        """One logical-call sample (calls + latency, and bytes on
        success) — async paths share the sync ``call()`` contract so the
        client metric families stay mutually consistent."""
        _M_CLIENT_CALLS.inc(service=self.service_name, method=method,
                            retried=retried)
        _M_CLIENT_LATENCY.observe(time.perf_counter() - t0,
                                  service=self.service_name, method=method)
        if sent is not None:
            self._count_bytes(sent, "sent", method=method)
        if received is not None:
            self._count_bytes(received, "received", method=method)

    def _async_chunked(self, method, payload, callback, error_callback,
                       timeout, wait_ready, retried: str = "0",
                       ctx=None, t0: Optional[float] = None,
                       outer: Optional["futures.Future"] = None):
        # ``ctx``/``t0`` arrive from call_async's caller thread (a grpc
        # completion thread has no useful contextvars state); direct
        # callers fall back to capturing here. ``retried="1"`` marks this
        # leg as the transparent continuation of a failed unary attempt —
        # one logical call either way, and ``outer`` (the caller-facing
        # wrapper future) resolves only with THIS leg's final outcome.
        if ctx is None:
            ctx = _trace.current_context()
        if t0 is None:
            t0 = time.perf_counter()

        def _run():
            try:
                with _trace.use_context(ctx):
                    result = self._call_chunked(method, payload, timeout,
                                                wait_ready)
            except Exception as exc:  # noqa: BLE001 - surfaced via callback
                _M_CLIENT_ERRORS.inc(service=self.service_name,
                                     method=method,
                                     code=_error_code_name(exc))
                self._record_client_call(method, retried, t0)
                if outer is not None:
                    self._resolve(outer, exc=exc)
                if error_callback is not None:
                    error_callback(exc)
                else:
                    logger.warning("async chunked RPC %s failed with no "
                                   "error_callback: %s", method, exc)
                return
            self._record_client_call(method, retried, t0,
                                     sent=len(payload),
                                     received=len(result))
            if outer is not None:
                self._resolve(outer, result=result)
            if callback is not None:
                callback(result)

        try:
            return self._stream_pool.submit(_run)
        except RuntimeError as exc:
            # pool already shut down (client.close() raced the oversize
            # retry issued from a grpc completion thread): the wrapper
            # future must still settle — a swallowed submit failure would
            # leave the caller blocked on it forever
            _M_CLIENT_ERRORS.inc(service=self.service_name, method=method,
                                 code="UNKNOWN")
            self._record_client_call(method, retried, t0)
            if outer is not None:
                self._resolve(outer, exc=exc)
            if error_callback is not None:
                error_callback(exc)
            else:
                logger.warning("async chunked RPC %s could not be "
                               "scheduled: %s", method, exc)
            return None

    def close(self) -> None:
        self._stream_pool.shutdown(wait=False)
        self._channel.close()
