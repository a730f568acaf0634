"""The shared async HTTP front-end (ISSUE 13): keep-alive reuse on the
selector path, idempotent concurrent teardown, the slow-loris read
deadline, and the idle-connection ceiling the reactor exists for.

These tests drive :class:`horovod_tpu._http.AsyncHTTPServer` directly —
the same server every endpoint (rendezvous KV, metrics, serving,
fleet router) now fronts itself with.
"""

import http.client
import json
import socket
import threading
import time

from horovod_tpu import _http


class _EchoHandler(_http.QuietHandler):
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        body = json.dumps({"path": self.path,
                           "thread": threading.current_thread().name}
                          ).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802
        length = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(length)
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _serve():
    return _http.start_server(_EchoHandler, port=0, addr="127.0.0.1",
                              name="test-http")


# ---------------------------------------------------------------------------
# keep-alive: one connection, many requests
# ---------------------------------------------------------------------------

def test_keepalive_connection_reused_across_requests():
    httpd = _serve()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", httpd.server_address[1], timeout=20)
        socks = set()
        for i in range(5):
            conn.request("GET", f"/r{i}")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["path"] == f"/r{i}"
            # http.client reuses self.sock only while the server honors
            # keep-alive; a close would force a fresh socket next request
            socks.add(id(conn.sock))
        assert len(socks) == 1, "server dropped a keep-alive connection"
        conn.close()
    finally:
        _http.stop_server(httpd)


def test_pipelined_requests_all_answered():
    """Two requests in one write: the second's bytes are already
    buffered in the handler's rfile, so the selector never fires for
    them — the worker must notice and keep serving."""
    httpd = _serve()
    try:
        with socket.create_connection(
                ("127.0.0.1", httpd.server_address[1]), timeout=20) as s:
            s.sendall(b"GET /a HTTP/1.1\r\nHost: x\r\n\r\n"
                      b"GET /b HTTP/1.1\r\nHost: x\r\n\r\n")
            s.settimeout(20)
            buf = b""
            # generous wall budget: the box running the full tier-1
            # suite is a loaded single core, and this asserts liveness,
            # not latency
            deadline = time.monotonic() + 20
            # (until both bodies are in: a response's head and body are
            # two writes, and the second body may trail its head)
            while buf.count(b"HTTP/1.1 200") < 2 or b"/b" not in buf:
                assert time.monotonic() < deadline, buf
                chunk = s.recv(65536)
                assert chunk, f"connection closed early: {buf!r}"
                buf += chunk
        assert b"/a" in buf and b"/b" in buf
    finally:
        _http.stop_server(httpd)


# ---------------------------------------------------------------------------
# teardown: concurrent + repeated stop_server
# ---------------------------------------------------------------------------

def test_stop_server_idempotent_under_concurrent_callers():
    httpd = _serve()
    errors = []

    def stopper():
        try:
            _http.stop_server(httpd)
        except Exception as e:  # noqa: BLE001 — the assertion below
            errors.append(e)

    threads = [threading.Thread(target=stopper) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert errors == []
    # and again, after it is already down
    _http.stop_server(httpd)
    assert not httpd._hvd_thread.is_alive()
    _http.stop_server(None)     # owners may stop a never-started endpoint


def test_stop_server_closes_parked_connections():
    httpd = _serve()
    conn = http.client.HTTPConnection(
        "127.0.0.1", httpd.server_address[1], timeout=5)
    conn.request("GET", "/warm")
    assert conn.getresponse().read()        # parked again after this
    _http.stop_server(httpd)
    sock = conn.sock
    sock.settimeout(5)
    assert sock.recv(1) == b"", "parked connection not closed on stop"
    conn.close()


# ---------------------------------------------------------------------------
# slow-loris: a stalled mid-request client is bounded by the read deadline
# ---------------------------------------------------------------------------

def test_slow_loris_request_bounded_by_read_deadline():
    httpd = _serve()
    httpd.read_timeout = 0.5        # applies to connections accepted next
    try:
        with socket.create_connection(
                ("127.0.0.1", httpd.server_address[1]), timeout=5) as s:
            # start a request, then stall: the partial bytes activate a
            # worker, whose blocking read must time out, not pin forever
            s.sendall(b"GET /stall HTTP/1.1\r\nHos")
            s.settimeout(5)
            t0 = time.monotonic()
            data = s.recv(1024)
            elapsed = time.monotonic() - t0
        assert data == b"", "server kept a stalled request open"
        # 0.5s deadline plus a loaded-box scheduling allowance — the
        # point is "bounded", not "instant"
        assert elapsed < 10.0, f"read deadline not enforced ({elapsed:.1f}s)"
    finally:
        _http.stop_server(httpd)


def test_idle_keepalive_connection_outlives_read_deadline():
    """The deadline bounds *started* requests; a connection idling
    between requests is a selector entry and must not be reaped."""
    httpd = _serve()
    httpd.read_timeout = 0.3
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", httpd.server_address[1], timeout=5)
        conn.request("GET", "/a")
        assert conn.getresponse().read()
        time.sleep(1.0)             # > 3x the read deadline, idle
        conn.request("GET", "/b")
        assert conn.getresponse().status == 200
        conn.close()
    finally:
        _http.stop_server(httpd)


# ---------------------------------------------------------------------------
# the reactor's reason to exist: idle connections cost fds, not threads
# ---------------------------------------------------------------------------

def test_thousand_idle_connections_without_a_thousand_threads():
    httpd = _serve()
    conns = []
    try:
        baseline = threading.active_count()
        for _ in range(1000):
            s = socket.create_connection(
                ("127.0.0.1", httpd.server_address[1]), timeout=10)
            conns.append(s)
        # all accepted and parked: a request on late connections round-trips
        deadline = time.monotonic() + 30
        for s in (conns[0], conns[500], conns[-1]):
            s.sendall(b"GET /ping HTTP/1.1\r\nHost: x\r\n\r\n")
            s.settimeout(10)
            buf = b""
            while b"\r\n\r\n" not in buf or b"/ping" not in buf:
                assert time.monotonic() < deadline, buf
                chunk = s.recv(65536)
                assert chunk, "server dropped an idle connection"
                buf += chunk
            assert b"200" in buf.split(b"\r\n", 1)[0]
        # the threaded baseline would need ~1000 threads here; the
        # reactor needs none for idle connections and a bounded burst of
        # short-lived workers for the three requests above
        assert threading.active_count() - baseline < 50
    finally:
        for s in conns:
            try:
                s.close()
            except OSError:
                pass
        _http.stop_server(httpd)
