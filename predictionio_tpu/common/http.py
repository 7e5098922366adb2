"""Minimal threaded HTTP service kit shared by all REST planes.

Parity role: the reference's ``common/`` module (akka-http ``Json4sSupport``,
``KeyAuthentication``) — the service plane stays REST (SURVEY.md §2.7); only
the compute plane moved to XLA.  Stdlib-only (no external web framework).
"""

from __future__ import annotations

import email.utils
import json
import re
import socket
import threading
import time
import urllib.parse
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Optional

from predictionio_tpu.common import faults as _faults
from predictionio_tpu.obs import tracing as _tracing


@dataclass
class Request:
    method: str
    path: str
    params: dict[str, str]  # query params (first value)
    headers: Any
    body: bytes
    match: Optional[re.Match] = None
    # the sampled obs trace riding this request (None when unsampled or
    # telemetry is not installed); handlers pass it to async stages
    trace: Any = None

    def json(self) -> Any:
        if not self.body:
            return None
        return json.loads(self.body.decode("utf-8"))

    def form(self) -> dict[str, str]:
        pairs = urllib.parse.parse_qsl(self.body.decode("utf-8"))
        return dict(pairs)


@dataclass
class Response:
    status: int = 200
    # JSON-serializable, str (text/html), bytes, or an ITERATOR of bytes —
    # iterators are sent with Transfer-Encoding: chunked, one HTTP chunk per
    # yielded piece, so multi-GB bulk pulls never materialize one body buffer
    body: Any = None
    content_type: Optional[str] = None
    headers: dict[str, str] = field(default_factory=dict)


def json_response(status: int, obj: Any) -> Response:
    return Response(status=status, body=obj)


# -- hot-loop response machinery --------------------------------------------
# The serve path writes ONE buffer per response: a pre-encoded status line +
# static headers, a per-second cached Date, Content-Length, then the payload
# — instead of BaseHTTPRequestHandler's one-write-per-header (each a
# syscall: wfile is unbuffered).

_SERVER_HDR = b"Server: pio-tpu\r\n"
_STATUS_LINES: dict[int, bytes] = {}
_CTYPE_HDRS = {
    "application/json; charset=utf-8": b"Content-Type: application/json; charset=utf-8\r\n",
    "text/html; charset=utf-8": b"Content-Type: text/html; charset=utf-8\r\n",
    "application/octet-stream": b"Content-Type: application/octet-stream\r\n",
}
_DATE_CACHE: tuple[int, bytes] = (0, b"")


def _status_line(status: int) -> bytes:
    line = _STATUS_LINES.get(status)
    if line is None:
        try:
            from http import HTTPStatus

            phrase = HTTPStatus(status).phrase
        except ValueError:
            phrase = ""
        line = f"HTTP/1.1 {status} {phrase}\r\n".encode("ascii")
        _STATUS_LINES[status] = line
    return line


def _date_hdr() -> bytes:
    global _DATE_CACHE
    now = int(time.time())
    sec, hdr = _DATE_CACHE
    if sec != now:
        hdr = ("Date: " + email.utils.formatdate(now, usegmt=True) + "\r\n").encode(
            "ascii"
        )
        # racing threads rebuild the same (second, header) pair; last
        # write wins and every value is correct, so no lock is needed
        _DATE_CACHE = (now, hdr)  # pio: ignore[race-global-write]
    return hdr


class _Server(ThreadingHTTPServer):
    # The stdlib default accept backlog (5) drops bursts of concurrent
    # connects with ConnectionResetError; the reference's akka-http server
    # has no such cliff, and `pio loadtest` needs >=64 concurrent.
    request_queue_size = 128
    daemon_threads = True


class HttpService:
    """Route table + threaded server; handlers get Request, return Response."""

    def __init__(self, name: str = "service"):
        self.name = name
        self.routes: list[tuple[str, re.Pattern, Callable[[Request], Response]]] = []
        # literal patterns (no capture groups / wildcards) dispatch through
        # one dict hit instead of the regex scan — the hot path for the
        # query server's fixed routes
        self._exact: dict[tuple[str, str], Callable[[Request], Response]] = {}
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # obs.Telemetry installed via Telemetry.install(service); the hot
        # loop pays ONE attribute check when absent
        self.telemetry = None

    def route(self, method: str, pattern: str):
        regex = re.compile("^" + pattern + "$")

        def deco(fn):
            self.routes.append((method.upper(), regex, fn))
            literal = pattern.replace(r"\.", ".")
            if not any(c in literal for c in "[](){}?*+|^$\\"):
                # routes are registered during service construction,
                # strictly before start() spawns the accept thread
                self._exact[(method.upper(), literal)] = fn  # pio: ignore[race-unguarded-rmw]
            return fn

        return deco

    def dispatch(self, req: Request) -> Response:
        fn = self._exact.get((req.method, req.path))
        if fn is not None:
            return fn(req)
        path_matched = False
        for method, regex, fn in self.routes:
            m = regex.match(req.path)
            if m:
                path_matched = True
                if method == req.method:
                    req.match = m
                    return fn(req)
        if path_matched:
            return json_response(405, {"message": "method not allowed"})
        return json_response(404, {"message": "not found"})

    # -- server lifecycle ---------------------------------------------------
    def start(
        self,
        host: str = "0.0.0.0",
        port: int = 7070,
        cert_path: Optional[str] = None,
        key_path: Optional[str] = None,
    ) -> int:
        """Start serving; TLS when cert/key paths are given (parity:
        common SSLConfiguration — the reference servers optionally serve
        HTTPS from a configured keystore)."""
        service = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # silence default stderr spam
                pass

            def parse_request(self):
                # request presence on the profiler's clock starts when the
                # request line has arrived (an idle keep-alive socket is
                # not a request).  pio_req., not pio.: obs/tracing.py
                self._t_parse = time.perf_counter()
                with _tracing.annotation("pio_req.parse"):
                    return super().parse_request()

            def _handle(self, method: str):
                # with pio_req.parse, "this request is in the server" up to
                # the answer's last byte; it has the trace's id, if any
                rid = self.headers.get(_tracing.TRACE_HEADER)
                with _tracing.annotation("pio_req.handle", id=rid or ""):
                    self._respond(method, rid)

            def _respond(self, method: str, rid: Optional[str]):
                parsed = urllib.parse.urlsplit(self.path)
                params = dict(urllib.parse.parse_qsl(parsed.query))
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                # the truncate flag is per-REQUEST, not per-connection: a
                # keep-alive socket must not carry a stale fault into a
                # response the seeded plan never scheduled
                self._fault_truncate = False
                # fault-injection shim (chaos tests, common/faults.py):
                # one None check when no plan is installed
                act = _faults.check(f"server:{service.name}:{parsed.path}")
                if act is not None:
                    if act.latency_s:
                        time.sleep(act.latency_s)
                    if act.kind == "drop":
                        # die without a response: the client sees a reset /
                        # RemoteDisconnected, like a crashed server process
                        self.close_connection = True
                        try:
                            self.connection.shutdown(socket.SHUT_RDWR)
                        except OSError:
                            pass
                        return
                    if act.kind == "error":
                        try:
                            self._send(
                                json_response(
                                    act.status, {"message": "injected fault"}
                                )
                            )
                        except (BrokenPipeError, ConnectionResetError):
                            self.close_connection = True
                        return
                    if act.kind == "truncate":
                        # flag for _send: cut a streamed body mid-frame
                        self._fault_truncate = True
                tel = service.telemetry
                trace = None
                if tel is not None:
                    t_req = time.perf_counter()
                    trace = tel.tracer.begin(
                        request_id=rid,
                        name=f"{method} {parsed.path}",
                    )
                    if trace is not None:
                        # request line arrived -> the trace is born: the
                        # headers' parsing and the body's read, which the
                        # trace's own wall starts after
                        trace.annotate(parse_ms=round(
                            (t_req - self._t_parse) * 1e3, 4))
                req = Request(
                    method=method,
                    path=parsed.path,
                    params=params,
                    headers=self.headers,
                    body=body,
                    trace=trace,
                )
                try:
                    if trace is not None:
                        # active-trace scope: downstream stage() calls and
                        # the storage client's header propagation see it
                        with _tracing.scope((trace,)):
                            resp = service.dispatch(req)
                    else:
                        resp = service.dispatch(req)
                except json.JSONDecodeError as e:
                    resp = json_response(400, {"message": f"invalid JSON: {e}"})
                except Exception as e:  # pragma: no cover - defensive
                    resp = json_response(500, {"message": str(e)})
                if trace is not None:
                    resp.headers.setdefault(
                        _tracing.TRACE_HEADER, trace.request_id
                    )
                try:
                    if tel is None:
                        self._send(resp)
                    else:
                        t_send = time.perf_counter()
                        try:
                            self._send(resp)
                        finally:
                            if trace is not None:
                                trace.add_stage(
                                    "serialize",
                                    time.perf_counter() - t_send,
                                )
                                trace.finish(status=resp.status)
                                tel.tracer.record(trace)
                            tel.observe_http(
                                method, parsed.path, resp.status,
                                time.perf_counter() - t_req,
                                (method, parsed.path) in service._exact,
                            )
                except (BrokenPipeError, ConnectionResetError):
                    # client went away mid-response; nothing to salvage
                    self.close_connection = True

            def _send(self, resp: Response):
                body = resp.body
                ctype = resp.content_type
                if hasattr(body, "__next__"):  # byte-iterator → chunked
                    self.send_response(resp.status)
                    self.send_header(
                        "Content-Type", ctype or "application/octet-stream"
                    )
                    self.send_header("Transfer-Encoding", "chunked")
                    for k, v in resp.headers.items():
                        self.send_header(k, v)
                    self.end_headers()
                    truncate = getattr(self, "_fault_truncate", False)
                    for piece in body:
                        if not piece:
                            # skip empties even when tearing: a zero-length
                            # cut would emit "0\r\n\r\n" — the chunked
                            # TERMINATOR — turning the injected tear into a
                            # cleanly-finished empty stream
                            continue
                        if truncate:
                            # chaos: tear the stream MID-piece (half a frame,
                            # no terminal chunk) — the client's framed reader
                            # must surface this as a truncated stream, never
                            # as a silently-short-but-valid result
                            cut = piece[: max(1, len(piece) // 2)]
                            self.wfile.write(
                                f"{len(cut):x}\r\n".encode() + cut + b"\r\n"
                            )
                            self.close_connection = True
                            try:
                                self.connection.shutdown(socket.SHUT_RDWR)
                            except OSError:
                                pass
                            return
                        self.wfile.write(
                            f"{len(piece):x}\r\n".encode() + piece + b"\r\n"
                        )
                    self.wfile.write(b"0\r\n\r\n")
                    return
                if isinstance(body, bytes):
                    payload = body
                    ctype = ctype or "application/octet-stream"
                elif isinstance(body, str):
                    payload = body.encode("utf-8")
                    ctype = ctype or "text/html; charset=utf-8"
                else:
                    payload = json.dumps(
                        body, separators=(",", ":")
                    ).encode("utf-8")
                    ctype = ctype or "application/json; charset=utf-8"
                # one write: pre-encoded head + payload. parse_request has
                # already decided keep-alive vs close from the request's
                # protocol/Connection header; we only advertise a close we
                # are about to perform so HTTP/1.1 clients don't re-use a
                # dying socket.
                ctype_hdr = _CTYPE_HDRS.get(ctype) or (
                    b"Content-Type: " + ctype.encode("latin-1") + b"\r\n"
                )
                head = [
                    _status_line(resp.status),
                    _SERVER_HDR,
                    _date_hdr(),
                    ctype_hdr,
                    b"Content-Length: " + str(len(payload)).encode("ascii") + b"\r\n",
                ]
                for k, v in resp.headers.items():
                    head.append(f"{k}: {v}\r\n".encode("latin-1"))
                if self.close_connection:
                    head.append(b"Connection: close\r\n")
                head.append(b"\r\n")
                self.wfile.write(b"".join(head) + payload)

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_DELETE(self):
                self._handle("DELETE")

            def do_PUT(self):
                self._handle("PUT")

        self._server = _Server((host, port), Handler)
        if cert_path:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(cert_path, key_path)
            self._server.socket = ctx.wrap_socket(
                self._server.socket, server_side=True
            )
        actual_port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name=f"{self.name}-http", daemon=True
        )
        self._thread.start()
        return actual_port

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    def serve_forever(self) -> None:
        if self._thread is not None:
            self._thread.join()
