"""Trim a profiler trace (``.xplane.pb``) to a small fixture: the first
``--ms`` milliseconds of chip 0's ``XLA Modules`` and ``XLA Ops`` lines and
the host's ``bench.*`` spans over the same time, every other plane and line
dropped.  Works on the protobuf wire format, so it needs no schema:

    XSpace  { repeated XPlane planes = 1; ... }
    XPlane  { int64 id = 1; string name = 2; repeated XLine lines = 3;
              map<int64, XEventMetadata> event_metadata = 4; ... }
    XLine   { ... string name = 2; int64 timestamp_ns = 3;
              repeated XEvent events = 4; ... }
    XEvent  { int64 metadata_id = 1; int64 offset_ps = 2; ... }
    XEventMetadata { int64 id = 1; string name = 2; ... }

    python trim.py <in.xplane.pb> <out.xplane.pb> --ms 300
"""
from __future__ import annotations

import argparse
from typing import Iterator, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return out, i


def _enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def fields(buf: bytes) -> Iterator[Tuple[int, int, object, bytes]]:
    """``(number, wire type, value, raw bytes of the whole field)``."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val, buf[start:i]


def _field(num: int, payload: bytes) -> bytes:
    return _enc_varint(num << 3 | 2) + _enc_varint(len(payload)) + payload


def _name(msg: bytes) -> str:
    for num, wt, val, _ in fields(msg):
        if num == 2 and wt == 2:
            return val.decode()
    return ""


def _trim_line(line: bytes, keep_event) -> bytes:
    out = bytearray()
    for num, wt, val, raw in fields(line):
        if num == 4 and wt == 2 and not keep_event(val):
            continue
        out += raw
    return bytes(out)


def _event_time_ps(event: bytes) -> int:
    for num, wt, val, _ in fields(event):
        if num == 2 and wt == 0:
            return val
    return 0


def _event_meta(event: bytes) -> int:
    for num, wt, val, _ in fields(event):
        if num == 1 and wt == 0:
            return val
    return 0


def trim(buf: bytes, ms: float) -> bytes:
    cutoff_ps = int(ms * 1e9)
    out = bytearray()
    for num, wt, plane, raw in fields(buf):
        if num != 1:
            out += raw
            continue
        name = _name(plane)
        if name not in ("/device:TPU:0", "/host:CPU"):
            continue
        names = {}
        for pn, pw, pv, _ in fields(plane):
            if pn == 4 and pw == 2:
                key = value = None
                for en, ew, ev, _ in fields(pv):
                    if en == 1:
                        key = ev
                    elif en == 2:
                        value = ev
                if key is not None and value is not None:
                    names[key] = _name(value)
        kept = bytearray()
        for pn, pw, pv, praw in fields(plane):
            if pn != 3:
                kept += praw
                continue
            lname = _name(pv)
            if name == "/device:TPU:0" and lname in ("XLA Modules", "XLA Ops"):
                kept += _field(3, _trim_line(pv, lambda e: _event_time_ps(e) < cutoff_ps))
            elif name == "/host:CPU":
                line = _trim_line(pv, lambda e: names.get(_event_meta(e), "").startswith("bench.")
                                  and _event_time_ps(e) < cutoff_ps)
                if any(n == 4 for n, _, _, _ in fields(line)):
                    kept += _field(3, line)
        out += _field(1, bytes(kept))
    return bytes(out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=300.0)
    args = ap.parse_args()
    with open(args.src, "rb") as f:
        buf = f.read()
    with open(args.dst, "wb") as f:
        f.write(trim(buf, args.ms))


if __name__ == "__main__":
    main()
