"""Which part of the model each device operation computes, from the scope
path the program writes into it, and the device's busy time by that part.

The programs open a ``jax.named_scope`` of one fixed vocabulary round each
part of the model (docs/DESIGN.md "Observability & tracing"): ``mlsl_embed``,
``mlsl_norm``, ``mlsl_attn``, ``mlsl_mlp``, ``mlsl_experts``, ``mlsl_conv``,
``mlsl_head``, ``mlsl_update``, ``mlsl_feed``; the collectives open
``mlsl_<kind>_<tag>``. JAX writes the scopes into each HLO instruction's
``op_name`` (``jit(step)/transpose(jvp(mlsl_head))/dot_general``), and the
profiler keeps it as the ``tf_op`` stat of the operation's event metadata,
which ``jax.profiler.ProfileData`` does not hand out: ``metadata_paths``
reads it from the profiler's file itself (the protocol buffer's wire
format, with nothing but Python), and ``op_paths`` lines the paths up with
the operations ``trace.from_xplane`` lists.

The rules of the reduction (``by_scope``):

- an operation is booked to the innermost ``mlsl_`` scope of its path; one
  under a collective's ``mlsl_<kind>_<tag>`` to ``comm``; one with no
  ``mlsl_`` scope, or no path, to ``_unscoped_``;
- its phase is ``backward`` where the path holds ``transpose(`` (what JAX
  writes under autodiff; the forward that ``jax.checkpoint`` recomputes
  inside the backward pass is written ``transpose(jvp(...))/checkpoint/
  rematted_computation/...`` and so is booked backward too), ``update``
  under ``mlsl_update``, else ``forward``;
- a fusion carries the path of its root instruction (XLA gives the fused
  instruction its root's metadata), so a fusion that crosses a scope's edge
  is booked whole to its root's scope;
- busy time is booked once: an instant the device spends inside nested
  events (a ``while`` and the operations of its body) goes to the innermost
  event that covers it, so the parts add up to the union of the intervals.

A trace without paths (an older profiler's file, or the paths not attached)
gives ``None`` everywhere; an executable built without scopes gives paths
with no ``mlsl_`` scope, booked to ``_unscoped_``. JAX's persistent compile
cache strips debug information before it hashes a program, so an executable
compiled without scopes is served from the cache to a build that has them.
"""

import re

from perf.lib import trace as trace_lib

VOCABULARY = ("embed", "norm", "attn", "mlp", "experts", "conv", "head",
              "update", "feed")
SCOPE = re.compile(r"mlsl_[A-Za-z0-9_]+")
PATH_STAT = "tf_op"
UNSCOPED, COMM = "_unscoped_", "comm"


# -- the profiler's file: event metadata of the device planes -------------------
#
# XSpace {planes = 1}; XPlane {name = 2, lines = 3, event_metadata = 4 (map
# int64 -> XEventMetadata), stat_metadata = 5 (map int64 -> XStatMetadata)};
# XEventMetadata {name = 2, stats = 5}; XStatMetadata {name = 2}; XStat
# {metadata_id = 1, str_value = 5, ref_value = 7: the id of a stat metadata
# whose name is the string}. Lines, the bulk of the file, are skipped whole.


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo, hi):
    """-> (field number, value): an int for a varint, (start, end) of the
    bytes for anything length-delimited or fixed."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = (i, i + 8), i + 8
        elif kind == 5:
            value, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield num, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of a map entry, or None."""
    for num, value in _fields(buf, *span):
        if num == 2:
            return value
    return None


def _plane(buf, lo, hi):
    """-> (device id, {event name: [(stat id, str or None, ref or None)]},
    {stat id: stat name}); None for a plane that is not a TPU's (its name,
    field 2, comes before the metadata)."""
    dev, events, stat_names = None, {}, {}
    for num, value in _fields(buf, lo, hi):
        if num == 2:
            m = trace_lib.DEVICE_PLANE.match(_text(buf, value))
            if not m:
                return None
            dev = m.group(1)
        elif num == 4:
            meta = _map_value(buf, value)
            if meta is None:
                continue
            ev_name, stats = None, []
            for n, v in _fields(buf, *meta):
                if n == 2:
                    ev_name = _text(buf, v)
                elif n == 5:
                    sid = text = ref = None
                    for sn, sv in _fields(buf, *v):
                        if sn == 1:
                            sid = sv
                        elif sn == 5:
                            text = _text(buf, sv)
                        elif sn == 7:
                            ref = sv
                    stats.append((sid, text, ref))
            if ev_name is not None:
                events.setdefault(ev_name, stats)
        elif num == 5:
            meta = _map_value(buf, value)
            if meta is None:
                continue
            sid = sname = None
            for n, v in _fields(buf, *meta):
                if n == 1:
                    sid = v
                elif n == 2:
                    sname = _text(buf, v)
            if sid is not None:
                stat_names[sid] = sname
    return (dev, events, stat_names) if dev is not None else None


def metadata_paths(raw):
    """The profiler's file (bytes) -> {device id: {event metadata name (the
    name ``ProfileEvent.name`` gives): scope path}} for the TPU planes."""
    buf = memoryview(raw)
    out = {}
    for num, value in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        plane = _plane(buf, *value)
        if plane is None:
            continue
        dev, events, stat_names = plane
        paths = {}
        for ev_name, stats in events.items():
            for sid, text, ref in stats:
                if stat_names.get(sid) == PATH_STAT:
                    path = text if text is not None else stat_names.get(ref)
                    if path:
                        paths[ev_name] = path
        out[dev] = paths
    return out


def op_paths(path):
    """The scope path of every operation ``trace.from_xplane`` lists, in its
    order, beside the operation's name, so that ``attach`` can check that
    the two line up: -> {device id: [[op name, path or None], ...]}. An
    event whose metadata holds no ``tf_op`` is looked up in its own stats,
    once a name (the profiler may put a stat on either)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    by_meta = metadata_paths(raw)
    data = ProfileData.from_serialized_xspace(raw)
    out = {}
    for plane in data.planes:
        m = trace_lib.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        known = by_meta.get(m.group(1), {})
        own = {}
        lines = list(plane.lines)
        named = {line.name: line for line in lines}
        op_lines = [named["XLA Ops"]] if "XLA Ops" in named else [
            line for line in lines if line.name not in trace_lib.NOT_OPS]
        rows = []
        for line in op_lines:
            for ev in line.events:
                p = known.get(ev.name)
                if p is None:
                    if ev.name not in own:
                        own[ev.name] = next((v for k, v in ev.stats
                                             if k == PATH_STAT and v), None)
                    p = own[ev.name]
                rows.append((int(ev.start_ns), trace_lib.op_name(ev.name), p))
        rows.sort(key=lambda r: r[0])
        out[m.group(1)] = [[name, p] for _, name, p in rows]
    return out


def attach(data, paths):
    """Put the paths beside the operations of ``from_xplane``'s dict, as
    ``data["devices"][id]["scopes"]`` (a list parallel to ``ops``), where
    the two list the same operations. -> the devices that got them."""
    done = []
    for dev, rows in paths.items():
        d = data["devices"].get(dev)
        if d is None or len(rows) != len(d["ops"]) or any(
                r[0] != e[0] for r, e in zip(rows, d["ops"])):
            continue
        d["scopes"] = [r[1] for r in rows]
        done.append(dev)
    return done


# -- the reduction ---------------------------------------------------------------


def scope_of(path):
    """-> (phase, scope) of one operation's path (None: no path)."""
    if path is None:
        return "forward", UNSCOPED
    names = SCOPE.findall(path)
    if not names:
        scope = UNSCOPED
    else:
        inner = names[-1][len("mlsl_"):]
        scope = inner if inner in VOCABULARY else COMM
    if "transpose(" in path:
        phase = "backward"
    elif "mlsl_update" in names:
        phase = "update"
    else:
        phase = "forward"
    return phase, scope


def self_ns(events):
    """Busy time of each event with the time of the events nested in it (or
    started after it while it runs) taken out: ``events`` [(start, dur)].
    -> [ns, ...] in their order, summing to the union of the intervals."""
    out = [0] * len(events)
    stack, t = [], None       # (end, index), innermost last
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    for i in order:
        s, d = events[i]
        while stack and t < s:
            end, j = stack[-1]
            if end <= t:
                stack.pop()
                continue
            upto = min(end, s)
            out[j] += upto - t
            t = upto
            if end <= s:
                stack.pop()
        stack.append((s + d, i))
        t = s
    while stack:
        end, j = stack.pop()
        if end > t:
            out[j] += end - t
            t = end
    return out


def by_scope(trace, dev=None, within=None):
    """The device's busy time in the traced window by (phase, scope), each
    with its op families: -> {(phase, scope): {"ns": n, "families": {family:
    ns}}}; ``within``: [[start, end], ...] to keep only the operations that
    start inside them (a program's runs). None where the trace carries no
    path (``attach`` was not called, or found none) or nothing is kept."""
    if trace is None or not trace.devices:
        return None
    dev = trace.devices[0] if dev is None else dev
    d = trace.data["devices"][dev]
    paths = d.get("scopes")
    if not paths or all(p is None for p in paths):
        return None
    kept = [(e, p) for e, p in zip(d["ops"], paths)
            if trace.lo <= e[1] < trace.hi]
    if within is not None:
        kept = [(e, p) for e, p in kept
                if any(lo <= e[1] < hi for lo, hi in within)]
    if not kept:
        return None
    times = self_ns([(e[1], e[2]) for e, _ in kept])
    out = {}
    for (e, p), ns in zip(kept, times):
        row = out.setdefault(scope_of(p), {"ns": 0, "families": {}})
        row["ns"] += ns
        fam = trace_lib.op_family(e[0])
        row["families"][fam] = row["families"].get(fam, 0) + ns
    return out


def share(parts, keep):
    """Percentage of the busy time in ``parts`` (``by_scope``) whose (phase,
    scope) ``keep`` accepts; None where nothing is accepted or there are no
    parts: a missing scope reads None, never 0."""
    if not parts:
        return None
    total = sum(r["ns"] for r in parts.values())
    hit = [r["ns"] for k, r in parts.items() if keep(*k)]
    if not total or not hit:
        return None
    return 100.0 * sum(hit) / total


def scoped_share(parts):
    """Percentage of the busy time under any ``mlsl_`` scope: 0 for an
    executable built without scopes, None where the trace has no path."""
    if not parts:
        return None
    total = sum(r["ns"] for r in parts.values())
    if not total:
        return None
    unscoped = sum(r["ns"] for (_, s), r in parts.items() if s == UNSCOPED)
    return 100.0 * (total - unscoped) / total


def table(parts, n=12):
    """The ``n`` largest (phase, scope) entries: -> [[phase, scope, seconds,
    largest op family, its seconds], ...]."""
    if not parts:
        return []
    rows = sorted(parts.items(), key=lambda kv: -kv[1]["ns"])[:n]
    out = []
    for (phase, scope), r in rows:
        fam, ns = max(r["families"].items(), key=lambda kv: kv[1])
        out.append([phase, scope, r["ns"] / 1e9, fam, ns / 1e9])
    return out


def family_table(parts, n=10):
    """The ``n`` op families with the most busy time, each with the (phase,
    scope) entries that hold it, largest first: -> [[family, seconds,
    [[phase, scope, seconds], ...]], ...]. It names the scope of a family
    that is no entry's largest (A.X-K1's ``multiply_reduce_fusion``)."""
    if not parts:
        return []
    held = {}
    for key, r in parts.items():
        for fam, ns in r["families"].items():
            held.setdefault(fam, {})[key] = ns
    rows = sorted(held.items(), key=lambda kv: -sum(kv[1].values()))[:n]
    return [[fam, sum(by.values()) / 1e9,
             [[ph, s, ns / 1e9] for (ph, s), ns in
              sorted(by.items(), key=lambda kv: -kv[1])]]
            for fam, by in rows]


def format_table(rows):
    return "\n".join(f"{p:>8} {s:<12} {sec:9.4f} s  largest {fam} {fs:.4f} s"
                     for p, s, sec, fam, fs in rows)
