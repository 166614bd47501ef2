"""A minimal in-repo RESP2 server: the store the contract tests and the
kill-the-store bench leg run RespStateStore against.

This container (and CI) has no Redis server and the project rule is zero
new dependencies, so the stub speaks just enough of the protocol for the
client's command set — PING, SELECT, GET, SET (NX/XX/PX/EX), DEL, MGET,
SADD/SREM/SMEMBERS, EXISTS, FLUSHALL — over a stdlib ThreadingTCPServer.
Expiry is lazy (checked at read/lock time), which is exactly the part of
``SET NX PX`` the client's advisory locks rely on. NOT a Redis: no
persistence, no replication, no pipelining guarantees beyond
one-request-one-reply per connection — a protocol-faithful crash dummy
that can be SIGKILLed and restarted to stage a store outage.

Run standalone (it prints a READY line once it listens):

    python -m bee_code_interpreter_fs_tpu.services.resp_stub --port 7379

or in-process for tests via ``RespStubServer``.
"""

from __future__ import annotations

import argparse
import socketserver
import threading
import time


class _Store:
    """One shared keyspace (the client's SELECT just switches a db index;
    the stub keeps per-db dicts so SELECT round-trips faithfully)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        # db -> key -> (value bytes | set[bytes], expires_at | None)
        self.dbs: dict[int, dict[bytes, tuple[object, float | None]]] = {}

    def db(self, index: int) -> dict:
        return self.dbs.setdefault(index, {})

    def live(self, db: dict, key: bytes):
        entry = db.get(key)
        if entry is None:
            return None
        value, expires = entry
        if expires is not None and time.monotonic() >= expires:
            del db[key]
            return None
        return value


class _Handler(socketserver.StreamRequestHandler):
    def handle(self) -> None:  # noqa: C901 — one branch per command, flat
        store: _Store = self.server.store  # type: ignore[attr-defined]
        db_index = 0
        while True:
            try:
                args = self._read_command()
            except (ConnectionError, OSError, ValueError):
                return
            if args is None:
                return
            if not args:
                continue
            cmd = args[0].upper()
            with store.lock:
                db = store.db(db_index)
                if cmd == b"PING":
                    self._simple("PONG")
                elif cmd == b"SELECT":
                    db_index = int(args[1])
                    self._simple("OK")
                elif cmd == b"FLUSHALL":
                    store.dbs.clear()
                    self._simple("OK")
                elif cmd == b"GET":
                    value = store.live(db, args[1])
                    self._bulk(value if isinstance(value, bytes) else None)
                elif cmd == b"MGET":
                    out = []
                    for key in args[1:]:
                        value = store.live(db, key)
                        out.append(value if isinstance(value, bytes) else None)
                    self._array(out)
                elif cmd == b"SET":
                    self._set(db, store, args)
                elif cmd == b"DEL":
                    removed = 0
                    for key in args[1:]:
                        if store.live(db, key) is not None:
                            del db[key]
                            removed += 1
                    self._int(removed)
                elif cmd == b"EXISTS":
                    self._int(
                        sum(
                            1
                            for key in args[1:]
                            if store.live(db, key) is not None
                        )
                    )
                elif cmd == b"SADD":
                    members = store.live(db, args[1])
                    if not isinstance(members, set):
                        members = set()
                    before = len(members)
                    members.update(args[2:])
                    db[args[1]] = (members, None)
                    self._int(len(members) - before)
                elif cmd == b"SREM":
                    members = store.live(db, args[1])
                    if not isinstance(members, set):
                        self._int(0)
                        continue
                    before = len(members)
                    members.difference_update(args[2:])
                    if members:
                        db[args[1]] = (members, None)
                    else:
                        db.pop(args[1], None)
                    self._int(before - len(members))
                elif cmd == b"SMEMBERS":
                    members = store.live(db, args[1])
                    if not isinstance(members, set):
                        self._array([])
                    else:
                        self._array(sorted(members))
                else:
                    self._error(
                        f"ERR unknown command '{cmd.decode(errors='replace')}'"
                    )

    def _set(self, db: dict, store: _Store, args: list[bytes]) -> None:
        key, value = args[1], args[2]
        nx = xx = False
        expires: float | None = None
        i = 3
        while i < len(args):
            opt = args[i].upper()
            if opt == b"NX":
                nx = True
            elif opt == b"XX":
                xx = True
            elif opt == b"PX":
                i += 1
                expires = time.monotonic() + int(args[i]) / 1000.0
            elif opt == b"EX":
                i += 1
                expires = time.monotonic() + int(args[i])
            else:
                self._error(f"ERR syntax error near {opt!r}")
                return
            i += 1
        exists = store.live(db, key) is not None
        if (nx and exists) or (xx and not exists):
            self._bulk(None)
            return
        db[key] = (value, expires)
        self._simple("OK")

    # ------------------------------------------------------------- protocol

    def _read_command(self) -> list[bytes] | None:
        line = self.rfile.readline()
        if not line:
            return None
        if not line.startswith(b"*"):
            # Inline command (telnet-style) — enough for hand-poking.
            return line.strip().split()
        count = int(line[1:].strip())
        args = []
        for _ in range(count):
            header = self.rfile.readline()
            if not header.startswith(b"$"):
                raise ValueError("malformed bulk header")
            length = int(header[1:].strip())
            data = self.rfile.read(length + 2)
            if len(data) != length + 2:
                raise ConnectionError("truncated bulk body")
            args.append(data[:-2])
        return args

    def _simple(self, text: str) -> None:
        self.wfile.write(f"+{text}\r\n".encode())

    def _error(self, text: str) -> None:
        self.wfile.write(f"-{text}\r\n".encode())

    def _int(self, value: int) -> None:
        self.wfile.write(f":{value}\r\n".encode())

    def _bulk(self, data: bytes | None) -> None:
        if data is None:
            self.wfile.write(b"$-1\r\n")
        else:
            self.wfile.write(b"$%d\r\n%s\r\n" % (len(data), data))

    def _array(self, items: list) -> None:
        self.wfile.write(b"*%d\r\n" % len(items))
        for item in items:
            self._bulk(item)


class RespStubServer:
    """In-process harness: ``with RespStubServer() as url:`` yields a
    ``redis://...`` URL RespStateStore connects to."""

    def __init__(self, port: int = 0) -> None:
        socketserver.ThreadingTCPServer.allow_reuse_address = True
        self.server = socketserver.ThreadingTCPServer(
            ("127.0.0.1", port), _Handler
        )
        self.server.daemon_threads = True
        self.server.store = _Store()  # type: ignore[attr-defined]
        self.port = self.server.server_address[1]
        self.url = f"redis://127.0.0.1:{self.port}"
        self._thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )

    def start(self) -> "RespStubServer":
        self._thread.start()
        return self

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()

    def __enter__(self) -> str:
        self.start()
        return self.url

    def __exit__(self, *exc) -> None:
        self.stop()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--port", type=int, default=0)
    args = parser.parse_args()
    stub = RespStubServer(port=args.port)
    stub.start()
    # The bench subprocess-spawns this and blocks on the READY line.
    print(f"READY {stub.port}", flush=True)
    try:
        stub._thread.join()
    except KeyboardInterrupt:
        stub.stop()


if __name__ == "__main__":
    main()
