"""A tiny writer of the profiler's XSpace protobuf, enough to record a
trace whose busy time, gaps and per-operation sums are known. Field
numbers are those of tensorflow/tsl's xplane.proto: XSpace.planes=1;
XPlane id=1 name=2 lines=3 event_metadata=4 stat_metadata=5 stats=6;
XLine id=1 name=2 timestamp_ns=3 events=4; XEvent metadata_id=1
offset_ps=2 duration_ps=3 stats=4; XEventMetadata id=1 name=2;
XStatMetadata id=1 name=2; XStat metadata_id=1 uint64_value=3."""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, value: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _plane(plane_id: int, name: str, lines: dict, stats: dict) -> bytes:
    """lines: {line name: [(event name, start_ns, duration_ns[, {stat
    name: uint64 value}])]}; stats: {stat name: uint64 value} on the plane
    itself."""
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    stat_names = list(stats) + sorted(
        {k for evs in lines.values() for ev in evs if len(ev) > 3
         for k in ev[3]} - set(stats))
    stat_meta = {n: i + 1 for i, n in enumerate(stat_names)}
    body = _int(1, plane_id) + _bytes(2, name.encode())
    for i, (line, events) in enumerate(lines.items()):
        ln = _int(1, i + 1) + _bytes(2, line.encode()) + _int(3, 0)
        for ev_name, start_ns, dur_ns, *ev_stats in events:
            ev = (_int(1, meta[ev_name]) + _int(2, start_ns * 1000)
                  + _int(3, dur_ns * 1000))
            for n, value in (ev_stats[0] if ev_stats else {}).items():
                ev += _bytes(4, _int(1, stat_meta[n]) + _int(3, value))
            ln += _bytes(4, ev)
        body += _bytes(3, ln)
    for n, i in meta.items():
        body += _bytes(4, _int(1, i) + _bytes(
            2, _int(1, i) + _bytes(2, n.encode())))
    for n, i in stat_meta.items():
        body += _bytes(5, _int(1, i) + _bytes(
            2, _int(1, i) + _bytes(2, n.encode())))
    for n, value in stats.items():
        body += _bytes(6, _int(1, stat_meta[n]) + _int(3, value))
    return body


def write(path: str, planes: list) -> None:
    """planes: [(name, lines, stats)]."""
    with open(path, "wb") as fh:
        for i, (name, lines, stats) in enumerate(planes):
            fh.write(_bytes(1, _plane(i + 1, name, lines, stats)))
