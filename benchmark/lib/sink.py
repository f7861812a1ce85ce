"""Follow the program's metrics sink while it trains, on the benchmark's clock.

The program writes one JSON record a line (``metrics_sink=jsonl:<path>``) and
flushes each.  A ``step`` record carries a loss the host has read, so the
device has finished every step up to it when it appears.  The follower reads
new lines as they land and stamps each with its own ``time.time()``: the
end-to-end numbers are taken on the benchmark's clock and never read from the
program's ``ts``.  The two are compared on a line of the run's output.

The window, from the records after the ``compile`` record (each covers one or
more whole dispatches):

    s_1 ... s_w      warm-up; ``w`` is the mix's ``window.warm_records``
    t0 = seen(s_w)   the window opens: every dispatch up to here is done
    s_w+1 ... s_m    the window: the records seen by t0 + seconds
    rate             steps of s_w+1..s_m x items a step / (seen(s_m) - t0)

so it covers whole dispatches only and nothing is extrapolated.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, Optional

Record = Dict[str, Any]
POLL_S = 0.002


class Follower(threading.Thread):
    """Tail ``path`` on a thread; ``on_record`` is called with each new
    record, stamped under ``"_seen"``, and ``on_tick`` at every poll, both
    on that thread."""

    def __init__(self, path: str, on_record: Callable[[Record], None],
                 on_tick: Callable[[], None] = lambda: None):
        super().__init__(name="bench-sink-follower", daemon=True)
        self.path = path
        self.on_record = on_record
        self.on_tick = on_tick
        self.records: List[Record] = []
        self._halt = threading.Event()
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self._follow()
        except BaseException as e:  # noqa: BLE001 - reported by the owner
            self.error = e

    def _follow(self) -> None:
        f = None
        buf = ""
        while True:
            self.on_tick()
            if f is None:
                try:
                    f = open(self.path)
                except FileNotFoundError:
                    if self._halt.wait(POLL_S):
                        return
                    continue
            chunk = f.read()
            if not chunk:
                if self._halt.is_set():
                    f.close()
                    return
                time.sleep(POLL_S)
                continue
            now = time.time()
            buf += chunk
            *lines, buf = buf.split("\n")
            for line in lines:
                if line.strip():
                    rec = json.loads(line)
                    rec["_seen"] = now
                    self.records.append(rec)
                    self.on_record(rec)

    def stop(self) -> None:
        """Read what is left and end the thread."""
        self._halt.set()
        self.join(timeout=30)


@dataclasses.dataclass
class Window:
    """The measured window of one run."""

    t0: float                 # when it opened, benchmark clock
    records: List[Record]     # s_w+1 ... s_m
    steps: List[int]          # training steps each record covers
    walls: List[float]        # seconds each record took, benchmark clock
    items_per_step: int

    @property
    def n_steps(self) -> int:
        return sum(self.steps)

    @property
    def wall_s(self) -> float:
        return sum(self.walls)

    @property
    def items_per_s(self) -> float:
        return self.n_steps * self.items_per_step / self.wall_s

    @property
    def ms_per_step(self) -> float:
        """Median over the window's records of wall time a step."""
        return statistics.median(
            1e3 * w / s for w, s in zip(self.walls, self.steps))

    def bad_steps(self) -> int:
        """Steps under a record whose loss is missing or not finite."""
        return sum(s for r, s in zip(self.records, self.steps)
                   if not _finite(r.get("loss")))

    def clock_skew_ms(self) -> float:
        """Largest distance between the benchmark's stamp and the
        program's ``ts`` over the window's records."""
        return max(abs(r["_seen"] - r["ts"]) for r in self.records) * 1e3


def _finite(v: Any) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def steps_after_compile(records: List[Record]) -> List[Record]:
    """The ``step`` records that follow the first ``compile`` record."""
    for i, r in enumerate(records):
        if r.get("kind") == "compile":
            return [s for s in records[i + 1:] if s.get("kind") == "step"]
    return []


def cut_window(records: List[Record], warm_records: int, seconds: float,
               items_per_step: int) -> Optional[Window]:
    """The window of a finished run, or None when the run did not reach
    one record past the warm-up."""
    steps = steps_after_compile(records)
    if len(steps) <= warm_records:
        return None
    opener = steps[warm_records - 1]
    t0 = opener["_seen"]
    inside = [s for s in steps[warm_records:] if s["_seen"] <= t0 + seconds]
    if not inside:
        return None
    prev = [opener] + inside[:-1]
    return Window(
        t0=t0, records=inside,
        steps=[int(r["global_step"]) - int(p["global_step"])
               for p, r in zip(prev, inside)],
        walls=[r["_seen"] - p["_seen"] for p, r in zip(prev, inside)],
        items_per_step=items_per_step)
