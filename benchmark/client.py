"""The client's side of a run: one consumer that reads `MatchOut` as it
grows and stamps when each input order's last record was fetched, and
the two producers the traffic kinds need — `saturate` (a bounded backlog
always waiting) and `paced` (an open loop on a fixed schedule, each
order timed from the time it was DUE). Stamped binary frames through
`TcpBroker.produce_frames`, the call `kme-loadgen --binary` uses.

One process, three threads (stream generation, consumer, producer): the
load comes from few threads so that it is steady."""

from __future__ import annotations

import bisect
import threading
import time

from kme_tpu.bridge.broker import BrokerError
from kme_tpu.bridge.tcp import TcpBroker
from kme_tpu.wire import encode_frames


class Stream:
    """The cell's messages, drawn in a background thread so that the
    preamble can be sent while the rest is still being generated."""

    def __init__(self, iterator):
        self.msgs: list = []
        self.done = False
        self.done_t = None          # clock at which the last was drawn
        self._it = iterator
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self):
        append = self.msgs.append
        for m in self._it:
            append(m)
        self.done_t = time.monotonic()
        self.done = True

    def wait_for(self, n: int) -> int:
        """Block until n messages exist (or the stream has ended);
        returns how many of the n there are."""
        while len(self.msgs) < n and not self.done:
            time.sleep(0.005)
        return min(n, len(self.msgs))


class Consumer:
    """Follows MatchOut. Every input message's output starts with one
    `IN` record, so counting them numbers the orders; `last_t[k]` is the
    clock at which the fetch holding order k's last record returned."""

    def __init__(self, host: str, port: int, pause_s: float = 0.0):
        self.cli = TcpBroker(host, port)
        # after a fetch that held records the consumer is busy for this
        # long (a consumer does something with what it read): without
        # it every single record the server produces wakes this
        # long-poll, and serving those fetches takes the serve loop's
        # interpreter lock once per record
        self.pause_s = pause_s
        self.last_t: list = []      # per order begun, in input order
        self.records = 0
        self.first_t = None         # first record of all
        self.data_t = 0.0           # last fetch that held records
        self.empty_t = 0.0          # last fetch that held none
        self.error = None
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    @property
    def begun(self) -> int:
        return len(self.last_t)

    def quiet(self) -> bool:
        """A long-poll came back empty after the last data."""
        return self.empty_t > self.data_t

    def _run(self):
        last_t = self.last_t
        while not self._stop:
            try:
                recs = self.cli.fetch_bin("MatchOut", self.records, 8192,
                                          timeout=0.25)
            except BrokerError as e:
                # before provisioning the topic is unknown; after the
                # server has gone the connection is
                self.error = e
                time.sleep(0.02)
                continue
            t = time.monotonic()
            if not recs:
                self.empty_t = t
                continue
            if self.first_t is None:
                self.first_t = t
            self.records += len(recs)
            if last_t and recs[0].key != "IN":
                last_t[-1] = t
            last_t.extend([t] * sum(1 for r in recs if r.key == "IN"))
            self.data_t = t
            if self.pause_s:
                time.sleep(self.pause_s)

    def stop(self):
        self._stop = True
        self._thread.join()
        self.cli.close()


class Producer:
    def __init__(self, host, port, stream: Stream, epoch: int, chunk: int):
        self.cli = TcpBroker(host, port)
        self.stream = stream
        self.epoch = epoch
        self.chunk = chunk
        self.sent = 0               # messages acknowledged by the broker
        self.longest_call_s = 0.0   # the slowest acknowledgement

    def send_to(self, hi: int) -> None:
        """Produce messages [sent, hi) in chunks; every produce must be
        acknowledged in full."""
        hi = self.stream.wait_for(hi)
        while self.sent < hi:
            lo, up = self.sent, min(self.sent + self.chunk, hi)
            t = time.monotonic()
            n, _last = self.cli.produce_frames(
                "MatchIn", None, encode_frames(self.stream.msgs[lo:up]),
                epoch=self.epoch, seq0=lo)
            self.longest_call_s = max(self.longest_call_s,
                                      time.monotonic() - t)
            if n != up - lo:
                raise RuntimeError(
                    f"the broker kept {n} of {up - lo} frames at {lo}")
            self.sent = up

    def close(self):
        self.cli.close()


def saturate(prod: Producer, cons: Consumer, traffic: dict, seconds: float,
             alive, on_open) -> dict:
    """Keep `lead_orders` ahead of the orders the consumer has seen
    begin. The window opens at the instant message `warmup_messages`
    completed (the engine is in steady state, pipeline full) or, where
    the stream is still being drawn by then, at the first completion
    after its end: nothing is generated inside the window, and the
    server is fed all the while (it ends itself when its input stays
    silent, so it is never left waiting for the generator). The window
    lasts `seconds`. Returns the window's facts."""
    warm, lead = traffic["warmup_messages"], traffic["lead_orders"]
    first = t_open = None
    drained = False
    while True:
        if first is None and prod.stream.done:
            first = max(warm, cons.begun + 1)
        if t_open is None and first is not None and cons.begun > first:
            t_open = cons.last_t[first - 1]
            on_open(t_open)
        if t_open is not None and time.monotonic() >= t_open + seconds:
            break
        alive()
        if prod.sent - cons.begun < lead:
            want = min(prod.sent + prod.chunk, cons.begun + lead)
            before = prod.sent
            prod.send_to(want)
            if prod.sent == before:     # the stream has run out
                drained = True
                if cons.begun >= prod.sent:
                    break
                time.sleep(0.001)
        else:
            time.sleep(0.001)
    return {"t_open": t_open, "first": first, "drained": drained}


def due_offsets(traffic: dict, seconds: float) -> list:
    """Seconds after the window opens at which each order is due.
    `spacing` is "even" (order j at j / rate) or a burst schedule
    {"kind": "bursts", "period_s", "burst_s", "burst_share"}: of each
    period's orders, `burst_share` are due evenly inside its first
    `burst_s` seconds and the rest evenly over the remainder, so the
    mean rate is still `rate_per_s`."""
    rate = float(traffic["rate_per_s"])
    spacing = traffic.get("spacing", "even")
    if spacing == "even":
        return [j / rate for j in range(int(rate * seconds))]
    if not (isinstance(spacing, dict) and spacing.get("kind") == "bursts"):
        raise ValueError(f"unknown spacing {spacing!r}")
    period, burst = float(spacing["period_s"]), float(spacing["burst_s"])
    per = int(rate * period)
    nb = int(per * float(spacing["burst_share"]))
    one = ([i * burst / max(nb, 1) for i in range(nb)]
           + [burst + i * (period - burst) / max(per - nb, 1)
              for i in range(per - nb)])
    out = [p * period + d for p in range(int(seconds // period) + 1)
           for d in one]
    return [d for d in out if d < seconds]


def paced(prod: Producer, cons: Consumer, traffic: dict, seconds: float,
          alive, on_open) -> dict:
    """Send the warm-up as a burst and let it drain (and the stream be
    drawn to its end: nothing is generated inside the window); then an
    open loop: each order is sent when it is DUE on the fixed schedule,
    whatever the server does. An order found late goes out with the
    others that are due by then."""
    warm = traffic["warmup_messages"]
    prod.send_to(warm)
    while not (cons.begun >= warm and cons.quiet() and prod.stream.done):
        alive()
        time.sleep(0.005)
    due = due_offsets(traffic, seconds)
    have = prod.stream.wait_for(warm + len(due)) - warm
    t_open = time.monotonic() + 0.05
    on_open(t_open)
    late = []
    j = 0
    while j < have:
        now = time.monotonic() - t_open
        if now < due[j]:
            time.sleep(due[j] - now)
            continue
        j2 = bisect.bisect_right(due, now, j, have)
        late.extend(now - due[i] for i in range(j, j2))
        prod.send_to(warm + j2)
        j = j2
        alive()
    return {"t_open": t_open, "first": warm, "due": due[:have],
            "late": late, "drained": have < len(due)}


KINDS = {"saturate": saturate, "paced": paced}
