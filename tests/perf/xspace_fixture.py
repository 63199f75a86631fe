"""A profiler's file made by hand: the few fields of the XSpace protocol
buffer that ``jax.profiler.ProfileData`` and ``perf/lib/scopes.py`` read,
written in the wire format (no TensorFlow)."""


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _bytes(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _stat(sid, text=None, ref=None):
    return _int(1, sid) + (_bytes(5, text) if text is not None else b"") \
        + (_int(7, ref) if ref is not None else b"")


def plane(name, ops, stat_style="str"):
    """One plane. ``ops``: [(HLO line, start ns, duration ns, tf_op or
    None)] on its "XLA Ops" line; each distinct line gets one event
    metadata, its path as the ``tf_op`` stat, held as a string
    (``stat_style`` "str") or as a reference to a stat metadata whose name
    is the string ("ref"), as the profiler interns strings; or on each
    event instead of the metadata ("event")."""
    stat_names = {1: "tf_op"}
    metas, events = {}, b""
    for line, start, dur, path in ops:
        if line not in metas:
            mid = len(metas) + 1
            body = _int(1, mid) + _bytes(2, line)
            if path is not None and stat_style != "event":
                if stat_style == "ref":
                    ref = 100 + mid
                    stat_names[ref] = path
                    body += _bytes(5, _stat(1, ref=ref))
                else:
                    body += _bytes(5, _stat(1, text=path))
            metas[line] = (mid, body)
        own = _bytes(4, _stat(1, text=path)) \
            if stat_style == "event" and path is not None else b""
        events += _bytes(4, _int(1, metas[line][0]) + _int(2, start * 1000)
                         + _int(3, dur * 1000) + own)
    out = _int(1, 1) + _bytes(2, name)
    out += _bytes(3, _int(1, 1) + _bytes(2, "XLA Ops") + _int(3, 0) + events)
    for mid, body in metas.values():
        out += _bytes(4, _int(1, mid) + _bytes(2, body))
    for sid, sname in stat_names.items():
        out += _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid) + _bytes(2, sname)))
    return out


def xspace(*planes):
    return b"".join(_bytes(1, p) for p in planes)
