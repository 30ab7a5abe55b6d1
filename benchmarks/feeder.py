"""The load generator: a child process that never imports JAX.

    python benchmarks/feeder.py '<json config>'

It makes every frame from the seed (benchmarks/gen.py), encodes frames
ahead of need on a helper thread so that encoding does not sit on the
send path, and writes u32-LE length-prefixed SWB1 frames to the tenants'
TCP gateways. A *beat* is one frame to every target, in target order,
all due at the same instant; beat `b` carries each fleet's frame `b`
(gen.py: slice `b % slices` of tick `b // slices`).

The parent drives it over stdin, one line a command:

    T                    send the next beat now (set-up: warm-up beats)
    W <start> <seconds>  run the window: `start` is a time.monotonic()
                         instant (one clock for every process on Linux)
    C                    closed loop only: one beat was seen published
    Q  (or EOF)          close the sockets and exit

and reads stdout: `READY` once connected with frames queued, `SENT <beat>`
after each `T`, and after a window one JSON line
`{"first_frame", "due": [...], "sent": [...]}`: for each beat of the
window the instant it was due and the instant its first byte went to the
socket, both on time.monotonic().

Open loop: beat j is due at start + j / frames_per_s, whatever the server
does. Closed loop: `inflight_frames` beats are due at start, and each
credit makes the next one due at the instant the credit was read.
"""

from __future__ import annotations

import json
import os
import queue
import select
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.gen import Fleet  # noqa: E402


class Lines:
    """Line reader over the raw stdin descriptor, with a timeout."""

    def __init__(self, fd: int = 0):
        self.fd, self.buf, self.eof = fd, b"", False

    def get(self, timeout: float | None) -> str | None:
        """The next line, or None on timeout; "Q" at end of file."""
        while b"\n" not in self.buf:
            if self.eof:
                return "Q"
            ready, _, _ = select.select([self.fd], [], [], timeout)
            if not ready:
                return None
            chunk = os.read(self.fd, 65536)
            if not chunk:
                self.eof = True
            self.buf += chunk
        line, _, self.buf = self.buf.partition(b"\n")
        return line.decode().strip()


def say(text: str) -> None:
    sys.stdout.write(text + "\n")
    sys.stdout.flush()


def main() -> int:
    cfg = json.loads(sys.argv[1])
    fleets = [Fleet(cfg["seed"], t["tenant"], t["devices"],
                    cfg["anomaly_rate"], cfg["anomaly_magnitude"],
                    t["frame_devices"])
              for t in cfg["targets"]]
    socks = []
    for t in cfg["targets"]:
        s = socket.create_connection(("127.0.0.1", t["port"]))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        socks.append(s)

    ahead: queue.Queue = queue.Queue(maxsize=int(cfg.get("ahead", 32)))
    stop = threading.Event()

    def encode_ahead() -> None:
        beat = int(cfg["first_frame"])
        while not stop.is_set():
            item = (beat, [f.frame(beat) for f in fleets])
            while not stop.is_set():
                try:
                    ahead.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            beat += 1

    encoder = threading.Thread(target=encode_ahead, daemon=True)
    encoder.start()
    while not ahead.full():
        time.sleep(0.005)

    def send_beat() -> tuple[int, float]:
        beat, frames = ahead.get()
        t_sent = time.monotonic()
        for s, frame in zip(socks, frames):
            s.sendall(frame)
        return beat, t_sent

    lines = Lines()
    say("READY")
    while True:
        cmd = lines.get(None)
        if cmd == "Q":
            break
        if cmd == "T":
            beat, _ = send_beat()
            say(f"SENT {beat}")
        elif cmd.startswith("W "):
            _, start, seconds = cmd.split()
            say(json.dumps(window(cfg, lines, send_beat, float(start),
                                  float(seconds))))
    stop.set()
    for s in socks:
        s.close()
    return 0


def window(cfg, lines: Lines, send_beat, start: float,
           seconds: float) -> dict:
    end = start + seconds
    due_at, sent_at, first = [], [], None

    def send(due: float) -> None:
        nonlocal first
        beat, t_sent = send_beat()
        first = beat if first is None else first
        due_at.append(due)
        sent_at.append(t_sent)

    if cfg["loop"] == "open":
        period = 1.0 / float(cfg["frames_per_s"])
        j = 0
        while (due := start + j * period) < end:
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            send(due)
            j += 1
    else:
        time.sleep(max(start - time.monotonic(), 0.0))
        for _ in range(int(cfg["inflight_frames"])):
            send(start)
        while (left := end - time.monotonic()) > 0:
            cmd = lines.get(left)
            if cmd == "C":
                now = time.monotonic()
                if now < end:
                    send(now)
            elif cmd == "Q":
                lines.buf = b"Q\n" + lines.buf    # main() sees it next
                break
    return {"first_frame": first, "due": due_at, "sent": sent_at}


if __name__ == "__main__":
    sys.exit(main())
