"""The program's own names in a profiler trace (``.xplane.pb``): the spans
it opens on the host, the device programs it launches, and the phase
scope of each device operation.

* Program spans are host events named by a span path of the program's
  tracer (``serve.call/runner.step/runner.dispatch``); a span goes by the
  last component of its path.  ``serve.put`` and ``serve.call`` carry the
  chunk's sequence number in their ``chunk`` stat; the spans nested in a
  ``serve.call`` belong to its chunk.
* Device programs are the ``XLA Modules`` events of each TPU plane, named
  ``jit_<program>(<program id>)``; the program's steps are ``jit_tilt_*``.
* An operation's phase is the innermost ``tilt.*`` component of its
  ``tf_op`` (the op's scope path, e.g. ``jit(tilt_sparse_steady)/cond/
  branch_6_fun/tilt.gather/concatenate``).  ``tf_op`` is a stat of the
  op's event metadata, which ``jax.profiler.ProfileData`` does not
  expose, so the device planes are read here from the protobuf wire
  format (``XSpace`` / ``XPlane`` / ``XLine`` / ``XEvent``).

Everything is clipped to the harness's ``bench.window`` span; the window,
union, gap and self-time arithmetic is ``trace.py``'s.  A trace without
the names (a program that opens no spans, names no steps or scopes no
phases) reads as ``None``, never 0.

    python3 bench/program_trace.py out/bench/trace/fraud.quiet

prints the summary of one trace as JSON.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import re
import statistics
import sys

import trace as trace_reduce

__all__ = ["load", "for_cell", "phase", "phase_s", "cover",
           "completion_lags", "host_call_s", "idle_by_span", "summary"]

MODULES_LINE = "XLA Modules"
STEP = re.compile(r"^jit_tilt_(?!obs_accum)")
_PHASE = re.compile(r"tilt\.[A-Za-z_]+")
_SPAN = re.compile(r"^(serve|runner)\.")


# -- protobuf wire format -----------------------------------------------------

def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, lo, hi):
    """``(field number, value)`` of the message in ``b[lo:hi]``: varints
    as ints, length-delimited fields as ``(start, end)`` offsets."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 5:
            v, i = None, i + 4
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, v


def _group(b, span) -> dict:
    out = collections.defaultdict(list)
    for f, v in _fields(b, *span):
        out[f].append(v)
    return out


def _text(b, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b, entries):
    """The value messages of a protobuf map field's entries."""
    return [v for e in entries for f, v in _fields(b, *e) if f == 2]


def _device_planes(b) -> dict:
    """``{device id: {"modules": [(name, start_ns, end_ns)], "ops":
    [(text, start_ns, end_ns, tf_op, program id)]}}`` of the TPU planes."""
    out = {}
    for f, span in _fields(b, 0, len(b)):
        if f != 1:                                  # XSpace.planes
            continue
        plane = _group(b, span)
        m = trace_reduce._DEVICE.match(
            _text(b, plane[2][0]) if plane[2] else "")
        if not m:
            continue
        stat_names = {}
        for md in _map_values(b, plane[5]):         # XStatMetadata
            g = _group(b, md)
            stat_names[g[1][0] if g[1] else 0] = (
                _text(b, g[2][0]) if g[2] else "")
        meta = {}
        for md in _map_values(b, plane[4]):         # XEventMetadata
            g = _group(b, md)
            stats = {}
            for st in g[5]:                          # XStat
                s = _group(b, st)
                name = stat_names.get(s[1][0] if s[1] else 0)
                if s[5]:
                    stats[name] = _text(b, s[5][0])
                elif s[7]:
                    stats[name] = stat_names.get(s[7][0])
                elif s[3] or s[4]:
                    stats[name] = (s[3] or s[4])[0]
            meta[g[1][0] if g[1] else 0] = (
                _text(b, g[2][0]) if g[2] else "", stats.get("tf_op"),
                stats.get("program_id"))
        dev = out.setdefault(int(m.group(1)), {"modules": [], "ops": []})
        for ln in plane[3]:                          # XLine
            line = _group(b, ln)
            lname = _text(b, line[2][0]) if line[2] else ""
            if lname not in (MODULES_LINE, trace_reduce.OPS_LINE):
                continue
            t0 = line[3][0] if line[3] else 0
            for ev in line[4]:                       # XEvent
                e = _group(b, ev)
                name, tf_op, prog = meta.get(e[1][0] if e[1] else 0,
                                             ("", None, None))
                s = t0 + (e[2][0] if e[2] else 0) // 1000
                end = s + (e[3][0] if e[3] else 0) // 1000
                if lname == MODULES_LINE:
                    dev["modules"].append((name, s, end))
                else:
                    dev["ops"].append((name, s, end, tf_op, prog))
    return out


def _host(path: str):
    """Program spans ``[(leaf name, start_ns, end_ns, chunk or None)]``
    and harness spans ``[(name, start_ns, end_ns)]`` of the host planes."""
    from jax.profiler import ProfileData
    spans, bench = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith("bench."):
                    bench.append((name, e.start_ns, e.end_ns))
                elif _SPAN.match(name):
                    leaf = name.rsplit("/", 1)[-1]
                    chunk = (dict(e.stats).get("chunk")
                             if leaf.startswith("serve.") else None)
                    spans.append((leaf, e.start_ns, e.end_ns, chunk))
    return spans, bench


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> dict:
    with open(path, "rb") as f:
        blob = f.read()
    spans, bench = _host(path)
    wins = [(s, e) for n, s, e in bench if n == trace_reduce.WINDOW]
    devices = {d: v for d, v in _device_planes(blob).items() if v["ops"]}
    return {"window": wins[0] if wins else None, "spans": spans,
            "bench": bench, "devices": devices}


def load(path: str) -> dict:
    """The parts of one trace the readers use, as plain data (cached)."""
    return _load(path, os.path.getmtime(path))


def for_cell(ctx):
    """The trace of the cell's traced run (``run.py`` writes it under
    ``<OUT>/trace/<cell>``), or ``None`` when there is none."""
    import run
    try:
        path = trace_reduce.find_xplane(
            os.path.join(run.OUT, "trace", ctx.cell["name"]))
    except FileNotFoundError:
        return None
    data = load(path)
    return data if data["window"] is not None else None


# -- device phases ------------------------------------------------------------

def phase(tf_op):
    """Innermost ``tilt.*`` scope of an op's ``tf_op`` (or ``None``)."""
    found = _PHASE.findall(tf_op or "")
    return found[-1] if found else None


def _programs(dev) -> dict:
    """``{program id: program name}`` from the module events' names."""
    out = {}
    for name, _, _ in dev["modules"]:
        head, _, pid = name.rstrip(")").partition("(")
        if pid.isdigit():
            out[int(pid)] = head
    return out


def phase_s(data, program=None):
    """``{phase: device self seconds}`` over the window, mean over the
    devices (``None`` for ops outside every phase), of every op or of the
    ops of programs whose name ``program`` matches.  ``None`` when no op
    has a phase: the trace holds no scopes."""
    lo, hi = data["window"]
    out = collections.Counter()
    for dev in data["devices"].values():
        names = _programs(dev)
        ops = [(phase(tf), s, e) for _, s, e, tf, pid in dev["ops"]
               if program is None or program.search(names.get(pid, ""))]
        for ph, sec in trace_reduce._self_times(ops, lo, hi).items():
            out[ph] += sec / len(data["devices"])
    if not any(ph for ph in out):
        return None
    return dict(out)


def cover(data, program=re.compile(r"^jit_tilt_sparse_")):
    """Share of the matching programs' device time (their module events,
    clipped to the window) spent in ops under some phase."""
    lo, hi = data["window"]
    ph = phase_s(data, program)
    total = sum(max(0.0, min(e, hi) - max(s, lo))
                for dev in data["devices"].values()
                for name, s, e in dev["modules"] if program.search(name))
    if ph is None or total <= 0:
        return None
    total *= 1e-9 / len(data["devices"])
    return sum(v for k, v in ph.items() if k) / total


# -- per chunk ----------------------------------------------------------------

def _chunks(data) -> dict:
    """``{chunk: {"call": (s, e), "block": (s, e), "put": seconds}}`` of
    the chunks whose ``serve.call`` starts in the window."""
    lo, hi = data["window"]
    calls = {c: (s, e) for n, s, e, c in data["spans"]
             if n == "serve.call" and c is not None and lo <= s < hi}
    out = {c: {"call": se, "put": 0.0} for c, se in calls.items()}
    blocks = sorted((s, e) for n, s, e, _ in data["spans"]
                    if n == "serve.block")
    for n, s, e, c in data["spans"]:
        if n == "serve.put" and c in out:
            out[c]["put"] += (e - s) * 1e-9
    for c, (cs, ce) in calls.items():
        inside = [b for b in blocks if cs <= b[0] and b[1] <= ce]
        if inside:
            out[c]["block"] = inside[-1]
    return out


def completion_lags(data) -> list:
    """Per chunk, seconds from the end of its step program on the device
    (the ``jit_tilt_*`` module launched in its ``serve.call``; the last
    device to finish) to the end of its ``serve.block``."""
    steps = [(s, e) for dev in data["devices"].values()
             for name, s, e in dev["modules"] if STEP.search(name)]
    out = []
    for rec in _chunks(data).values():
        if "block" not in rec:
            continue
        cs, ce = rec["call"]
        ends = [e for s, e in steps if cs <= s <= ce]
        if ends:
            out.append((rec["block"][1] - max(ends)) * 1e-9)
    return out


def host_call_s(data) -> list:
    """Per chunk, host seconds in its ``serve.put`` and ``serve.call``
    outside its ``serve.block``."""
    return [r["put"] + (r["call"][1] - r["call"][0]
                        - (r["block"][1] - r["block"][0])) * 1e-9
            for r in _chunks(data).values() if "block" in r]


# -- idle time ----------------------------------------------------------------

def idle_by_span(data) -> dict:
    """Device idle seconds in the window (mean over the devices) by the
    innermost program or harness span covering each gap's middle."""
    lo, hi = data["window"]
    host = [(n, s, e) for n, s, e, _ in data["spans"]] + data["bench"]
    name = trace_reduce._namer(host, lo, hi)
    out = collections.Counter()
    for dev in data["devices"].values():
        busy = trace_reduce._union(((s, e) for _, s, e, _, _ in dev["ops"]),
                                   lo, hi)
        for s, e in trace_reduce._gaps(busy, lo, hi):
            out[name((s + e) / 2)] += (e - s) * 1e-9 / len(data["devices"])
    return dict(out)


def _median_ms(xs):
    return 1e3 * statistics.median(xs) if xs else None


def summary(data) -> dict:
    """What one trace shows, per chunk in ms where per chunk."""
    lo, hi = data["window"]
    n = len({c for c in _chunks(data)}) or None
    ph = phase_s(data) or {}
    modules = collections.Counter()
    for dev in data["devices"].values():
        for name, s, e in dev["modules"]:
            modules[name.partition("(")[0]] += (
                max(0.0, min(e, hi) - max(s, lo)) * 1e-9
                / len(data["devices"]))
    per = (lambda v: 1e3 * v / n) if n else (lambda v: None)
    return {"window_s": (hi - lo) * 1e-9, "chunks": n,
            "phase_ms": {str(k): per(v) for k, v in sorted(
                ph.items(), key=lambda kv: -kv[1])},
            "module_ms": {k: per(v) for k, v in modules.most_common()},
            "sparse_cover": cover(data),
            "completion_lag_ms": _median_ms(completion_lags(data)),
            "host_call_ms": _median_ms(host_call_s(data)),
            "idle_s": dict(sorted(idle_by_span(data).items(),
                                  key=lambda kv: -kv[1]))}


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = load(path)
    if data["window"] is None:
        raise SystemExit(f"no {trace_reduce.WINDOW!r} span in {path}")
    print(json.dumps(summary(data)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
