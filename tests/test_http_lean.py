"""The HTTP front end without the stdlib's per-request machinery
(`tools/server.py`: `SyncHandler.parse_request`, `_send`).

A request is read in one pass over its request line and header lines,
with no `email.parser`; a response leaves in one `sendall`. What the
lean parser cannot take goes the stdlib's way, told from the request's
bytes alone. These tests hold both to `BaseHTTPRequestHandler` on the
same bytes: (a) what the lean parser sets, (b) what it must hand over,
(c) the bytes of a response against the stdlib's writers, (d) one
`sendall` a response, (e) the `lean` / `stdlib` counts on the request's
root phase.

    python -m pytest tests/test_http_lean.py -q -p no:cacheprovider
"""

from __future__ import annotations

import email.utils
import http.client
import io
import json
import socket
import threading
import time
import types
from http.server import BaseHTTPRequestHandler

import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs.trace import TRACE_HEADER, parse_header
from diamond_types_tpu.qos.classes import QOS_HEADER, classify_headers
from diamond_types_tpu.read.path import MIN_VERSION_HEADER
from diamond_types_tpu.tools import server as server_mod
from diamond_types_tpu.tools.server import (DocStore, SyncHandler, _Headers,
                                            _Server)
from diamond_types_tpu.wire.frames import WIRE_CTYPE, WIRE_HEADER

# every name the program asks `self.headers` for, and a few it does not
ASKED = ("Content-Length", "Accept", "Connection", "Expect", TRACE_HEADER,
         WIRE_HEADER, QOS_HEADER, MIN_VERSION_HEADER,
         "X-DT-Proxied", "X-DT-Replication", "X-DT-Lease-Epoch", "Host",
         "Accept-Encoding", "Content-Type", "X-Not-Sent")


class Sock:
    """A socket double: the request's bytes to read, every `sendall`
    kept apart."""

    def __init__(self, data: bytes) -> None:
        self.data = data
        self.sent = []

    def makefile(self, mode, _bufsize=None):
        assert mode == "rb"
        return io.BytesIO(self.data)

    def sendall(self, data) -> None:
        self.sent.append(bytes(data))


def drive(cls, raw: bytes):
    """One connection's worth of `cls` over `raw`: the handler as its
    constructor left it (set up, handled, finished) and the socket."""
    sock = Sock(raw)
    return cls(sock, ("127.0.0.1", 0), types.SimpleNamespace()), sock


def probe(base):
    """`base`'s parsing alone: both verbs read the body and answer 204
    through the stdlib's writers."""
    class Probe(base):
        body = None

        def log_message(self, *a):
            pass

        def do_GET(self):
            n = int(self.headers.get("Content-Length") or 0)
            self.body = self.rfile.read(n)
            self.send_response(204)
            self.end_headers()
        do_POST = do_GET
    return Probe


LeanProbe = probe(SyncHandler)
StdlibProbe = probe(BaseHTTPRequestHandler)


def as_http_client_sends(method: str, path: str, body: bytes = None) -> bytes:
    """The bytes `bench/gen.py`'s `request` puts on the wire."""
    conn = http.client.HTTPConnection("127.0.0.1", 8008)
    conn.sock = Sock(b"")
    conn.request(method, path, body=body)
    return b"".join(conn.sock.sent)


def req(line: str, *headers: str, body: bytes = b"", eol: str = "\r\n"):
    return (eol.join((line,) + headers) + eol + eol).encode("latin-1") + body


EDIT = json.dumps({"agent": "w0", "version": [["w0", 41]],
                   "ops": [{"kind": "ins", "pos": 3, "text": "abc"}]}).encode()
BINARY = bytes(range(256)) + b"\r\n\r\nX: y\r\n" + bytes(range(255, -1, -1))

LEAN = {
    "the generator's own edit": as_http_client_sends(
        "POST", "/doc/x/edit", EDIT),
    "the generator's own get": as_http_client_sends("GET", "/doc/x"),
    "a query string": req(
        "GET /doc/x?max_staleness=0.5&x=%20y HTTP/1.1", "Host: h",
        "Accept: application/openmetrics-text; version=1.0.0",
        f"{MIN_VERSION_HEADER}: w0:41,w1:7"),
    "mixed-case names": req(
        "POST /doc/x/edit HTTP/1.1", "hOsT: h", "content-LENGTH: 3",
        "x-dt-qos: BULK", "X-dt-TRACE: 0af7651916cd43dd-b7ad6b71-1",
        body=b"{ }"),
    "a name sent twice": req(
        "POST /doc/x/push HTTP/1.1", "X-DT-QoS: bulk", "Content-Length: 0",
        "x-dt-qos: catchup", "Content-Length: 7"),
    "the peers' headers": req(
        "POST /doc/x/push HTTP/1.1", "X-DT-Trace: 0af7651916cd43dd-b7ad-0",
        "X-DT-Wire: v1", "X-DT-QoS: catchup", "X-DT-Lease-Epoch: 12",
        "X-DT-Proxied: 1", "X-DT-Replication: 1", "Content-Length: 2",
        body=b"ok"),
    "a binary push body": req(
        "POST /doc/x/push HTTP/1.1", "X-DT-Wire: v1",
        "Content-Type: application/x-dt-wire",
        f"Content-Length: {len(BINARY)}", body=BINARY),
    "HTTP/1.0": req("GET /doc/x HTTP/1.0", "Host: h"),
    "HTTP/1.1": req("GET /doc/x HTTP/1.1", "Host: h"),
    "an empty header block": req("GET /metrics HTTP/1.1"),
    "no blank line before the end": b"GET /doc/x HTTP/1.0\r\nAccept: a/b",
    "a carriage return, then the end": b"GET /doc/x HTTP/1.0\r\nAccept: a/b\r",
    "bare line feeds": req("POST /doc/x/at HTTP/1.0", "Content-Length: 2",
                           "X-DT-QoS: bulk", body=b"{}", eol="\n"),
    "blanks around a value": req(
        "GET /doc/x HTTP/1.1", "Accept:text/plain", "X-DT-QoS: \t bulk \t ",
        "X-DT-Proxied:", "X-DT-Replication:    "),
    "bytes above ASCII in a value": req(
        "GET /doc/x HTTP/1.1", "Accept: caf\xe9 \xa0", "X-DT-Wire: \x85v1"),
    "a colon in a value": req("GET /doc/x HTTP/1.1", "Host: [::1]:8008",
                              "Accept: a:b: c"),
    "two slashes": req("GET //evil.example/doc/x HTTP/1.1", "Host: h"),
    "Connection: close": req("GET /doc/x HTTP/1.1", "Connection: Close"),
    "Connection: keep-alive": req("GET /doc/x HTTP/1.1",
                                  "connection: Keep-Alive"),
    "blanks in the request line": req("GET \t /doc/x   HTTP/1.1 ", "Host: h"),
    "99 headers": req("GET /doc/x HTTP/1.1",
                      *(f"X-{i}: {i}" for i in range(99))),
    "a line of exactly the limit": req(
        "GET /doc/x HTTP/1.1", "Accept: " + "a" * (65536 - 10)),
}

# what the lean parser must hand to the stdlib's, with the status the
# stdlib answers (204: it reached the handler). A request the stdlib
# takes for HTTP/0.9 (no version it accepts read yet) is answered with
# no head: None where the handler's 204 leaves nothing on the wire, 0
# where an error's page is all there is
NOT_LEAN = {
    "a folded header": (req(
        "GET /doc/x HTTP/1.1", "Accept: text/plain,", "\tapplication/json",
        "X-DT-QoS: bulk"), 204),
    "a continuation first": (req(
        "GET /doc/x HTTP/1.1", " Accept: text/plain", "X-DT-QoS: bulk"), 204),
    "no colon": (req(
        "GET /doc/x HTTP/1.1", "Accept: a/b", "no colon here",
        "X-DT-QoS: bulk"), 204),
    "an empty name": (req(
        "GET /doc/x HTTP/1.1", ": nameless", "X-DT-QoS: bulk"), 204),
    "a blank in a name": (req(
        "GET /doc/x HTTP/1.1", "X-DT-QoS : bulk", "Accept: a/b"), 204),
    "an envelope line": (req(
        "GET /doc/x HTTP/1.1", "From me: x", "Accept: a/b"), 204),
    "a byte above ASCII in a name": (req(
        "GET /doc/x HTTP/1.1", "Acc\xe9pt: a/b", "X-DT-QoS: bulk"), 204),
    "a bare carriage return": (req(
        "GET /doc/x HTTP/1.1", "Accept: a/b\rX-DT-QoS: bulk",
        "X-DT-Wire: v1"), 204),
    "an over-long line": (req(
        "GET /doc/x HTTP/1.1", "Accept: " + "a" * (65537 - 10)), 431),
    "100 headers and the blank line": (req(
        "GET /doc/x HTTP/1.1", *(f"X-{i}: {i}" for i in range(100))), 431),
    "101 headers": (req(
        "GET /doc/x HTTP/1.1", *(f"X-{i}: {i}" for i in range(101))), 431),
    "HTTP/0.9": (b"GET /doc/x\r\n", None),
    "HTTP/0.9 with another verb": (b"POST /doc/x/edit\r\n", 0),
    "Expect: 100-continue": (req(
        "POST /doc/x/edit HTTP/1.1", "Expect: 100-continue",
        "Content-Length: 2", body=b"{}"), 204),
    "Transfer-Encoding: chunked": (req(
        "POST /doc/x/edit HTTP/1.1", "transfer-encoding: chunked",
        body=b"2\r\n{}\r\n0\r\n\r\n"), 204),
    "HTTP/2.0": (req("GET /doc/x HTTP/2.0", "Host: h"), 0),
    "HTTP/1.01": (req("GET /doc/x HTTP/1.01", "X-DT-QoS: bulk"), 204),
    "not a version": (req("GET /doc/x FTP/1.1", "Host: h"), 0),
    "four words": (req("GET /doc/x y HTTP/1.1", "Host: h"), 400),
    "an empty request line": (b"\r\n", None),
    "nothing at all": (b"", None),
}


def status_of(sock):
    """The status of the one response on the wire (None: nothing was
    sent; 0: a body with no head, as HTTP/0.9 is answered)."""
    out = b"".join(sock.sent)
    if not out:
        return None
    return int(out.split(b" ", 2)[1]) if out.startswith(b"HTTP/") else 0


def same_parse(lean, ref):
    """Everything `BaseHTTPRequestHandler.parse_request` sets and every
    answer the program asks of `headers`."""
    for attr in ("command", "path", "request_version", "requestline",
                 "close_connection", "raw_requestline", "body"):
        assert getattr(lean, attr) == getattr(ref, attr), attr
    for name in ASKED:
        for asked in (name, name.lower(), name.upper()):
            assert lean.headers.get(asked) == ref.headers.get(asked), asked
            assert lean.headers.get(asked, "-") == ref.headers.get(asked, "-")
            assert lean.headers[asked] == ref.headers[asked], asked
            assert (asked in lean.headers) == (asked in ref.headers), asked
    assert classify_headers(lean.headers) == classify_headers(ref.headers)

    def trace(h):
        ctx = parse_header(h.headers.get(TRACE_HEADER))
        return ctx and (ctx.trace_id, ctx.span_id, ctx.sampled)
    assert trace(lean) == trace(ref)


@pytest.mark.parametrize("case", list(LEAN))
def test_the_lean_parser_sets_what_the_stdlib_would(case):
    lean, sock = drive(LeanProbe, LEAN[case])
    ref, ref_sock = drive(StdlibProbe, LEAN[case])
    assert status_of(ref_sock) == 204 == status_of(sock)
    assert lean._parsed == "lean" and type(lean.headers) is _Headers
    same_parse(lean, ref)


def test_the_table_of_lean_requests_says_what_it_is_meant_to():
    """The cases above are about these answers, not only about their
    being equal on both sides."""
    h = drive(LeanProbe, LEAN["the generator's own edit"])[0]
    assert (h.command, h.path, h.request_version) == (
        "POST", "/doc/x/edit", "HTTP/1.1")
    assert h.body == EDIT and h.headers["content-length"] == str(len(EDIT))
    h = drive(LeanProbe, LEAN["a name sent twice"])[0]
    assert h.headers.get(QOS_HEADER) == "bulk"          # the first wins
    assert h.headers.get("Content-Length") == "0"
    h = drive(LeanProbe, LEAN["a binary push body"])[0]
    assert h.body == BINARY
    h = drive(LeanProbe, LEAN["blanks around a value"])[0]
    assert h.headers.get(QOS_HEADER) == "bulk \t "
    assert h.headers.get("X-DT-Proxied") == "" and "x-dt-proxied" in h.headers
    assert h.headers.get("X-Not-Sent") is None and h.headers["Nope"] is None
    assert drive(LeanProbe, LEAN["two slashes"])[0].path == "/evil.example/doc/x"
    assert drive(LeanProbe, LEAN["a query string"])[0].path.endswith("x=%20y")


@pytest.mark.parametrize("case", list(NOT_LEAN))
def test_what_the_lean_parser_cannot_take_gets_the_stdlibs_answer(case):
    raw, status = NOT_LEAN[case]
    lean, sock = drive(LeanProbe, raw)
    ref, ref_sock = drive(StdlibProbe, raw)
    assert status_of(ref_sock) == status == status_of(sock)
    assert lean._parsed == "stdlib"
    # the same response, to the byte, but for the clock in `Date`
    assert len(sock.sent) == len(ref_sock.sent)
    for got, want in zip(sock.sent, ref_sock.sent):
        assert undated(got) == undated(want)
    assert (lean.body is None) == (ref.body is None)
    if ref.body is not None:                # it reached the handler
        assert not isinstance(lean.headers, _Headers)
        assert lean.headers.items() == ref.headers.items()
        same_parse(lean, ref)


def undated(response: bytes) -> bytes:
    head, sep, body = response.partition(b"\r\n\r\n")
    return b"\r\n".join(
        line for line in head.split(b"\r\n")
        if not line.startswith(b"Date: ")) + sep + body


def sender(base, code, body, ctype, extra):
    class Sender(base):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if ctype is None:
                self._send(code, body, extra=extra)
            else:
                self._send(code, body, ctype, extra)
    return Sender


class ParentSend(SyncHandler):
    """`_send` as it was: the stdlib's writers, two `sendall`s."""

    def _send(self, code, body, ctype="application/json", extra=None):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)


def parsed(sock):
    """The response as `http.client` reads it."""
    reply = http.client.HTTPResponse(Sock(b"".join(sock.sent)))
    reply.begin()
    return reply, reply.read()


RESPONSES = {
    "200 an edit's answer": (200, b'{"version": [["w0", 49]]}', None, None),
    "200 a page": (200, "<p>caf\xe9</p>".encode("utf8"),
                   "text/html; charset=utf-8", None),
    "200 a frame, no-store": (200, BINARY, WIRE_CTYPE,
                              {"Cache-Control": "no-store"}),
    "200 a read's headers in order": (
        200, b"text", "text/plain; charset=utf-8",
        {"Cache-Control": "no-store", "X-DT-Version": "w0:41,w1:7",
         "X-DT-Staleness": "0.000", "X-DT-Served-By": "127.0.0.1:8008"}),
    "200 an empty body": (200, b"", None, {}),
    "400 bad op": (400, b'{"error": "bad op"}', None, None),
    "404": (404, b"{}", None, None),
    "409 fenced": (409, b'{"error": "fenced", "max_epoch": 3}', None, None),
    "429 shed": (429, b'{"error": "shed", "qos": "bulk"}', None,
                 {"Retry-After": "1.500", "Cache-Control": "no-store"}),
    "299 a code with no reason": (299, b"?", None, None),
}


@pytest.mark.parametrize("case", list(RESPONSES))
def test_a_response_is_the_parents_bytes_in_one_sendall(case):
    raw = LEAN["the generator's own get"]
    new_sock = drive(sender(SyncHandler, *RESPONSES[case]), raw)[1]
    old_sock = drive(sender(ParentSend, *RESPONSES[case]), raw)[1]
    assert len(new_sock.sent) == 1                      # (d)
    assert len(old_sock.sent) == 2
    new, new_body = parsed(new_sock)
    old, old_body = parsed(old_sock)
    assert (new.version, new.status, new.reason) == (
        old.version, old.status, old.reason)
    assert new.status == RESPONSES[case][0]
    assert new_body == old_body == RESPONSES[case][1]
    # names, values and order; `Date` within a second of the parent's
    assert [k for k, _ in new.getheaders()] == [k for k, _ in old.getheaders()]
    for (name, value), (_, old_value) in zip(new.getheaders(),
                                             old.getheaders()):
        if name == "Date":
            apart = (email.utils.parsedate_to_datetime(value)
                     - email.utils.parsedate_to_datetime(old_value))
            assert abs(apart.total_seconds()) <= 1
        else:
            assert value == old_value, name
    assert undated(new_sock.sent[0]) == undated(b"".join(old_sock.sent))


def test_an_answer_to_http_09_is_the_body_alone():
    h, sock = drive(sender(SyncHandler, 200, b"text", None, None),
                    b"GET /doc/x\r\n")
    ref_sock = drive(sender(ParentSend, 200, b"text", None, None),
                     b"GET /doc/x\r\n")[1]
    assert h._parsed == "stdlib" and h.request_version == "HTTP/0.9"
    assert sock.sent == ref_sock.sent == [b"text"]


def test_the_date_is_formatted_once_a_second(monkeypatch):
    now = [1_790_000_000.25]
    monkeypatch.setattr(server_mod, "time",
                        types.SimpleNamespace(time=lambda: now[0]))
    formatted = []

    class Counting(sender(SyncHandler, 200, b"{}", None, None)):
        def date_time_string(self, timestamp=None):
            formatted.append(timestamp)
            return super().date_time_string(timestamp)

    def date():
        sock = drive(Counting, LEAN["the generator's own get"])[1]
        return parsed(sock)[0].getheader("Date")

    first = date()
    now[0] += 0.5
    assert date() == first and len(formatted) == 1
    now[0] += 0.5                               # the next second
    assert date() == email.utils.formatdate(1_790_000_001, usegmt=True)
    assert date() != first and len(formatted) == 2
    # one cache a handler class: another class formats its own
    drive(sender(SyncHandler, 200, b"{}", None, None),
          LEAN["the generator's own get"])
    assert Counting._dated[0] == 1_790_000_001


def store_with_clocks():
    store = DocStore(None)
    store.obs = Observability()
    return store


def test_an_edit_is_answered_in_one_sendall_by_the_real_handler():
    store = store_with_clocks()
    handler = type("Handler", (SyncHandler,), {"store": store})
    body = json.dumps({"agent": "w0", "version": [], "ops": [
        {"kind": "ins", "pos": 0, "text": "hello"}]}).encode()
    h, sock = drive(handler, as_http_client_sends(
        "POST", "/doc/one/edit", body))
    assert len(sock.sent) == 1 and h._parsed == "lean"
    reply, answer = parsed(sock)
    assert reply.status == 200
    assert json.loads(answer) == {"version": [["w0", 4]]}
    assert reply.getheader("Content-Length") == str(len(answer))
    assert [k for k, _ in reply.getheaders()] == [
        "Server", "Date", "Content-Type", "Content-Length"]
    # a refusal and a read too
    for raw, status in (
            (as_http_client_sends("POST", "/doc/one/edit", b"{"), 400),
            (as_http_client_sends("POST", "/nowhere", b"{}"), 404),
            (as_http_client_sends("GET", "/doc/one"), 200)):
        h, sock = drive(handler, raw)
        assert len(sock.sent) == 1 and parsed(sock)[0].status == status
    assert parsed(sock)[1] == b"hello"
    counts = store.obs.phases.snapshot()["phases"]
    assert counts["http.edit"]["counts"]["lean"] == 2
    assert counts["http.get"]["counts"] == {"lean": 1}


def raw_exchange(port: int, raw: bytes) -> bytes:
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(raw)
        out = b""
        while chunk := s.recv(65536):
            out += chunk
    return out


def test_the_root_phase_counts_which_parser_took_each_request():
    """(e) a mixed run against a live server: the generator's own
    requests are the lean parser's, a folded header and an `Expect`
    are the stdlib's, one count a request on its root phase."""
    store = store_with_clocks()
    handler = type("Handler", (SyncHandler,), {"store": store})
    httpd = _Server(("127.0.0.1", 0), handler)
    httpd.store = store
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def edit(i, *headers):
        body = json.dumps({"agent": "w0", "version": [["w0", i - 1]] if i
                           else [], "ops": [
                               {"kind": "ins", "pos": i, "text": "x"}]})
        return req("POST /doc/mix/edit HTTP/1.1", "Host: h", *headers,
                   f"Content-Length: {len(body)}", body=body.encode())
    try:
        for i in range(3):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.request("POST", "/doc/mix/edit", body=edit(i).partition(
                b"\r\n\r\n")[2])
            assert conn.getresponse().status == 200
            conn.close()
        for i, extra in ((3, ("X-Folded: a,", "  b")),
                         (4, ("Expect: 100-continue",))):
            assert raw_exchange(port, edit(i, *extra)).startswith(
                b"HTTP/1.0 200 OK\r\n")
        assert raw_exchange(port, b"GET /doc/mix\r\n\r\n") == b"xxxxx"  # 0.9
        assert raw_exchange(port, req("GET /doc/mix HTTP/1.0")).endswith(
            b"\r\n\r\nxxxxx")
        # refused before a handler ran: no root phase, so no count
        assert raw_exchange(port, req(
            "GET /doc/mix HTTP/1.1", *(f"X-{i}: {i}" for i in range(100)))
        ).startswith(b"HTTP/1.0 431 ")
        table = store.obs.phases
        deadline = time.monotonic() + 10.0

        def counts():
            rows = table.snapshot()["phases"]
            return {name: rows.get(name, {}).get("counts", {})
                    for name in ("http.edit", "http.get")}
        want = {"http.edit": {"lean": 3, "stdlib": 2, "len_hit": 4,
                              "len_miss": 1},
                "http.get": {"lean": 1, "stdlib": 1}}
        while counts() != want and time.monotonic() < deadline:
            time.sleep(0.02)            # rows are written after the answer
        assert counts() == want
        assert table.snapshot()["phases"]["http.edit"]["count"] == 5
    finally:
        httpd.shutdown()
        httpd.server_close()
