"""The load generator: one child process, one thread, keep-alive HTTP
over localhost. It never imports JAX (the harness holds the chip) and
never imports the program.

``open`` sends request n at ``start + offsets[n]`` whether or not earlier
ones were answered, and times each from when it was DUE, so a stall is
charged to every request it delayed; how late the generator itself ran
(sent - due) is kept beside the latencies. ``closed`` keeps a fixed
number of callers, each sending its next request when its last is
answered, until the window closes.

Run as ``python3 benchmark/lib/loadgen.py <params.json>``: connects,
prints ``ready``, reads the start time from stdin (``time.monotonic()``,
which all processes of one machine share), runs, and writes
``<out>.npz`` (due, sent, done, status, user per request, all relative
to the start; body offsets) and ``<out>.bodies``.

:func:`drive` is the loop; it touches the world only through a transport
(``slots``, ``send``, ``poll``, ``reset``) and a clock, so the schedule
and its accounting are tested against a fake of both.
"""

from __future__ import annotations

import gc
import json
import os
import selectors
import socket
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

#: status of a request that got no answer: the server closed the
#: connection, or nothing came within the timeout
CLOSED, TIMED_OUT = -1, -2

_HEAD = (
    "POST /queries.json HTTP/1.1\r\nHost: bench\r\n"
    "Content-Type: application/json\r\nContent-Length: {}\r\n\r\n"
)


def request_bytes(user: int, num: int) -> bytes:
    body = json.dumps({"user": f"u{user}", "num": num}).encode()
    return _HEAD.format(len(body)).encode() + body


def drive(
    mode: str,
    total: int,
    offsets: Optional[np.ndarray],
    seconds: float,
    timeout_s: float,
    transport,
    clock: Callable[[], float],
    start: float,
) -> Dict[str, np.ndarray]:
    """Send up to ``total`` requests and wait for every answer. Times
    are returned relative to ``start``; a request never sent has NaNs
    and is cut off the end of the arrays."""
    due = start + offsets if mode == "open" else np.full(total, np.nan)
    end = start + seconds
    sent = np.full(total, np.nan)
    done = np.full(total, np.nan)
    status = np.zeros(total, np.int16)
    bodies: List[bytes] = [b""] * total
    idle = list(range(transport.slots))
    flying: Dict[int, int] = {}  # slot -> request
    nxt = 0
    while True:
        now = clock()
        if mode == "open":
            while nxt < total and due[nxt] <= now and idle:
                slot = idle.pop()
                flying[slot], sent[nxt] = nxt, now
                transport.send(slot, nxt)
                nxt += 1
            over = nxt >= total
            wait = due[nxt] - now if (not over and idle) else 0.05
        else:
            while idle and now < end and nxt < total:
                slot = idle.pop()
                flying[slot], sent[nxt], due[nxt] = nxt, now, now
                transport.send(slot, nxt)
                nxt += 1
            over = now >= end or nxt >= total
            wait = 0.05
        if over and not flying:
            break
        # epoll rounds a timeout up to whole milliseconds: poll without
        # waiting when the next request is due sooner than that
        for slot, code, body in transport.poll(
            0.0 if wait < 0.002 else min(wait - 0.001, 0.05)
        ):
            n = flying.pop(slot, None)
            if n is None:
                continue
            done[n], status[n], bodies[n] = clock(), code, body
            idle.append(slot)
        now = clock()
        for slot, n in list(flying.items()):
            # from when it was due AND from when it was sent: a request
            # that waited for a free connection is late, not yet lost
            if now - sent[n] > timeout_s:
                done[n], status[n] = now, TIMED_OUT
                del flying[slot]
                transport.reset(slot)
                idle.append(slot)
    return {
        "due": due[:nxt] - start, "sent": sent[:nxt] - start,
        "done": done[:nxt] - start, "status": status[:nxt],
        "bodies": bodies[:nxt],
    }


class HttpTransport:
    """``slots`` keep-alive connections; one request in flight on each."""

    def __init__(self, host: str, port: int, slots: int, users: np.ndarray, num: int):
        self.addr, self.slots, self.users, self.num = (host, port), slots, users, num
        self.sel = selectors.DefaultSelector()
        self.socks: List[socket.socket] = [None] * slots
        self.bufs = [b""] * slots
        for slot in range(slots):
            self._open(slot)

    def _open(self, slot: int) -> None:
        sock = socket.create_connection(self.addr)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.socks[slot], self.bufs[slot] = sock, b""
        self.sel.register(sock, selectors.EVENT_READ, slot)

    def reset(self, slot: int) -> None:
        self.sel.unregister(self.socks[slot])
        self.socks[slot].close()
        self._open(slot)

    def send(self, slot: int, n: int) -> None:
        self.socks[slot].sendall(request_bytes(int(self.users[n]), self.num))

    def poll(self, timeout: float) -> List[Tuple[int, int, bytes]]:
        out = []
        for key, _ in self.sel.select(timeout):
            slot = key.data
            try:
                chunk = self.socks[slot].recv(65536)
            except OSError:
                chunk = b""
            if not chunk:
                self.reset(slot)
                out.append((slot, CLOSED, b""))
                continue
            self.bufs[slot] += chunk
            answer = self._whole(slot)
            if answer is not None:
                out.append((slot,) + answer)
        return out

    def _whole(self, slot: int) -> Optional[Tuple[int, bytes]]:
        buf = self.bufs[slot]
        head_end = buf.find(b"\r\n\r\n")
        if head_end < 0:
            return None
        length = 0
        for line in buf[:head_end].decode("latin-1").split("\r\n")[1:]:
            key, _, value = line.partition(":")
            if key.strip().lower() == "content-length":
                length = int(value)
        need = head_end + 4 + length
        if len(buf) < need:
            return None
        self.bufs[slot] = buf[need:]
        return int(buf[9:12]), buf[head_end + 4 : need]

    def close(self) -> None:
        for sock in self.socks:
            sock.close()


def main() -> None:
    with open(sys.argv[1]) as f:
        p = json.load(f)
    if p.get("cpu") is not None:
        os.sched_setaffinity(0, {p["cpu"]})
    users = np.load(p["users"])
    offsets = np.load(p["offsets"]) if p["mode"] == "open" else None
    transport = HttpTransport(p["host"], p["port"], p["connections"], users, p["num"])
    if p.get("warm_s"):
        # the server's handler threads, its batcher and these sockets,
        # once, before the window: a short closed loop over the same users
        drive("closed", len(users), None, p["warm_s"], p["timeout_s"],
              transport, time.monotonic, time.monotonic())
    print("ready", flush=True)
    start = float(sys.stdin.readline())
    gc.disable()
    got = drive(
        p["mode"], len(users), offsets, p["seconds"], p["timeout_s"],
        transport, time.monotonic, start,
    )
    transport.close()
    bodies = got.pop("bodies")
    np.savez(
        p["out"] + ".npz", users=users[: len(bodies)],
        body_offsets=np.cumsum([0] + [len(b) for b in bodies]), **got,
    )
    with open(p["out"] + ".bodies", "wb") as f:
        f.write(b"".join(bodies))


if __name__ == "__main__":
    main()
