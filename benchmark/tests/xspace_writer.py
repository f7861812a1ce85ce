"""Write a small ``.xplane.pb`` by hand: just enough of the XSpace protobuf
(tsl/profiler/protobuf/xplane.proto) for ``jax.profiler.ProfileData`` to read
back planes, lines and events with a name, a start and a duration.

Used to build the fixtures under ``benchmark/fixtures`` and to cut a recorded
trace down to a few steps (``cut_trace.py``).  Field numbers: XSpace.planes=1;
XPlane.id=1 name=2 lines=3 event_metadata=4 (map: key=1, value=2);
XLine.id=1 name=2 timestamp_ns=3 events=4; XEvent.metadata_id=1 offset_ps=2
duration_ps=3; XEventMetadata.id=1 name=2.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

# (name, start_ns, duration_ns)
Ev = Tuple[str, float, float]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def plane(plane_id: int, name: str,
          lines: Sequence[Tuple[str, Sequence[Ev]]]) -> bytes:
    """One XPlane from ``[(line name, [(event name, start_ns, dur_ns)])]``."""
    ids: Dict[str, int] = {}
    body = _field(1, plane_id) + _field(2, name)
    for line_id, (line_name, events) in enumerate(lines, 1):
        msg = _field(1, line_id) + _field(2, line_name) + _field(3, 0)
        for ev_name, start_ns, dur_ns in events:
            mid = ids.setdefault(ev_name, len(ids) + 1)
            msg += _field(4, _field(1, mid)
                          + _field(2, int(round(start_ns * 1000)))
                          + _field(3, int(round(dur_ns * 1000))))
        body += _field(3, msg)
    for ev_name, mid in ids.items():
        body += _field(4, _field(1, mid)
                       + _field(2, _field(1, mid) + _field(2, ev_name)))
    return body


def write(path: str, planes: List[bytes]) -> None:
    with open(path, "wb") as f:
        for p in planes:
            f.write(_field(1, p))
