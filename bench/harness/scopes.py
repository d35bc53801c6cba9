"""Device time per name scope of the program, from a profiler trace.

The program's device code runs under ``jax.named_scope`` names:
``replay`` (the LWW reconstruction, with ``scatter`` and ``decide``
inside it) and ``measure``.  Each op of a profiled device is put under
the scope path of the HLO instruction it ran, read from one of:

* the op's ``tf_op`` stat in the trace (its XLA ``op_name``), where
  that names a scope;
* otherwise the ``op_name`` metadata of the program's optimized HLO,
  which the trace keeps per program (the ``Hlo Proto`` stats of its
  ``/host:metadata`` plane).  A fusion takes the scope of its root, or,
  where the root carries no metadata (a scatter the compiler rewrote),
  of the nearest instruction before the root that does.

``reduce`` gives device seconds per top-level scope with an
``unscoped`` remainder (they add up to the busy time ``profile.reduce``
reports for the same window) and per full scope path
(``replay/decide``).  ``from_xplane`` reads the lists from a
``.xplane.pb``; the rest works on the lists alone, so a test can feed
it a synthetic trace.  Where the program has no scopes, every op is
``unscoped`` and readers report nothing.
"""
from __future__ import annotations

import glob
import heapq
import os
import re

from harness import profile
from harness.cell import log

UNSCOPED = "unscoped"

# ``vmap(measure)`` -> ``measure``; ``jit(f)`` is a function boundary
_WRAPPED = re.compile(r"^([\w.\-]*)\((.*)\)$")
_FUNCTION = frozenset({"jit", "pjit", "xla_pmap", "shard_map"})
# control flow and call wrappers the compiler writes into op names
_CONTROL = frozenset({"while", "body", "cond", "closed_call", "core_call",
                      "checkpoint", "remat", "custom_jvp_call",
                      "custom_vjp_call", "scan"})
_BRANCH = re.compile(r"^branch_\d+_fun$")


def scope_path(op_name: str | None) -> tuple[str, ...]:
    """The user name scopes in an XLA ``op_name``:
    ``jit(f)/vmap(jit(g))/replay/decide/jit(_where)/select_n`` ->
    ``("replay", "decide")``.  The last component is the primitive."""
    if not op_name:
        return ()
    out = []
    for part in op_name.split(";")[0].split("/")[:-1]:
        while part:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = None if m.group(1) in _FUNCTION else m.group(2)
        if part and part not in _CONTROL and not _BRANCH.match(part):
            out.append(part)
    return tuple(out)


# ---------------------------------------------------------------------------
# HLO text: instruction -> scope path
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
def hlo_scopes(text: str) -> dict[str, tuple[str, ...]]:
    """Scope path of every instruction of one HLO module's text (printed
    with metadata).  A fusion's path is its root's, or, where the root
    carries no metadata (a scatter the compiler rewrote), that of the
    nearest instruction before the root that does."""
    comps: dict[str, list] = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            on = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            cur.append((m.group(2), bool(m.group(1)),
                        scope_path(on.group(1)) if on else (),
                        calls.group(1) if calls else None))

    def fused(comp: str, depth: int = 0) -> tuple[str, ...]:
        insts = comps.get(comp, [])
        order = ([i for i in insts if i[1]]
                 + [i for i in reversed(insts) if not i[1]])
        for _, _, path, calls in order:       # the root first
            if path:
                return path
            if calls and depth < 4:
                p = fused(calls, depth + 1)
                if p:
                    return p
        return ()

    return {name: path or (fused(calls) if calls else ())
            for insts in comps.values()
            for name, _, path, calls in insts}


# ---------------------------------------------------------------------------
# Reading a profiler trace
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields (fixed-width ones skipped)."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _stat_value(stat: dict):
    v = stat.get(5, stat.get(3, stat.get(4, stat.get(7))))
    if isinstance(v, bytes):
        return v.decode(errors="replace")
    if 4 in stat and v >= 1 << 63:                    # int64
        v -= 1 << 64
    return v


def _planes(raw: bytes, keep):
    """The planes of an XSpace whose name ``keep`` accepts, each as
    ``(name, event metadata, lines)``: event metadata
    ``{id: (name, display name, {stat: value})}`` and lines ``[(name,
    timestamp_ns, [(metadata id, offset_ps, duration_ps)])]`` (a
    reference-valued stat reads as the name it refers to)."""
    for fno, plane in _fields(raw):                   # XSpace.planes
        if fno != 1:
            continue
        fields = list(_fields(plane))
        name = next((v.decode() for f, v in fields if f == 2), "")
        if not keep(name):
            continue
        stat_names = {}
        for f, entry in fields:                       # XPlane.stat_metadata
            if f == 5:
                e = dict(_fields(entry))
                stat_names[e.get(1)] = dict(_fields(e.get(2, b""))).get(
                    2, b"").decode()
        meta = {}
        for f, entry in fields:                       # XPlane.event_metadata
            if f != 4:
                continue
            e = dict(_fields(entry))
            m = list(_fields(e.get(2, b"")))
            stats = {}
            for k, stat in m:
                if k == 5:
                    st = dict(_fields(stat))
                    v = _stat_value(st)
                    if 7 in st:
                        v = stat_names.get(v, v)
                    stats[stat_names.get(st.get(1), "")] = (
                        st[6] if 6 in st else v)
            meta[e.get(1)] = (
                next((v.decode() for k, v in m if k == 2), ""),
                next((v.decode() for k, v in m if k == 4), ""), stats)
        lines = []
        for f, line in fields:                        # XPlane.lines
            if f != 3:
                continue
            lf = list(_fields(line))
            events = []
            for k, ev in lf:
                if k == 4:
                    e = dict(_fields(ev))
                    events.append((e.get(1), e.get(2, 0), e.get(3, 0)))
            lines.append((next((v.decode() for k, v in lf if k == 2), ""),
                          dict(lf).get(3, 0), events))
        yield name, meta, lines


def hlo_text(module_proto: bytes) -> str | None:
    """An optimized HLO module's text with its op-name metadata."""
    try:
        from jax._src.lib import _jax, xla_client
        opts = _jax.HloPrintOptions()
        opts.print_metadata = True
        return (xla_client.XlaComputation(module_proto).get_hlo_module()
                .to_string(opts))
    except Exception:  # noqa: BLE001 — a jax without these: no text
        return None


_PROGRAM = re.compile(r"^(.*)\((-?\d+)\)$")
_HLO_OP = re.compile(r"^%?([\w.\-]+) = ")


def from_xplane(path: str, window: str = "bench.profile") -> dict:
    """Device ops of a ``.xplane.pb`` with their scope paths:
    ``{"ops": [(start_ns, dur_ns, device, module, path)], "start_ns",
    "end_ns", "devices", "route"}`` on the trace's own clock; the window
    is the host interval named ``window``.  A path comes from the op's
    ``tf_op`` stat (its XLA ``op_name``) where that names a scope, else
    from the program's HLO; ``route`` says which were used."""
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        raw = fh.read()
    host = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.duration_ns) for e in line.events
                         if e.name == window]
    if not host:
        raise ValueError(f"no {window!r} interval in {path}")
    start, dur = max(host, key=lambda x: x[1])
    planes = list(_planes(raw, lambda n: n == "/host:metadata"
                          or profile._DEVICE_PLANE.match(n)))
    # the programs' optimized HLO, by program id: ``jit_f(<id>)``
    programs = {}
    for name, meta, _ in planes:
        if name == "/host:metadata":
            for pname, _, stats in meta.values():
                m = _PROGRAM.match(pname)
                proto = stats.get("Hlo Proto")
                if m and isinstance(proto, bytes):
                    module = dict(_fields(proto)).get(1)  # HloProto
                    programs[int(m.group(2))] = (m.group(1), module)
    texts: dict = {}
    ops, devices, route = [], 0, set()
    for name, meta, lines in planes:
        if not profile._DEVICE_PLANE.match(name):
            continue
        devices += 1
        for lname, t0, events in lines:
            if lname != "XLA Ops":
                continue
            for mid, off_ps, dur_ps in events:
                ename, display, st = meta.get(mid, ("", "", {}))
                pid = st.get("program_id")
                module, proto = programs.get(pid, (st.get("hlo_module", ""),
                                                   None))
                p = scope_path(str(st.get("tf_op") or "").rsplit(":", 1)[0])
                if p:
                    route.add("tf_op")
                elif proto is not None:
                    if pid not in texts:
                        text = hlo_text(proto)
                        texts[pid] = hlo_scopes(text) if text else {}
                    m = _HLO_OP.match(ename)
                    op = st.get("hlo_op") or display or (
                        m.group(1) if m else ename)
                    p = texts[pid].get(str(op), ())
                    if p:
                        route.add("hlo")
                ops.append((int(t0 + off_ps / 1000), int(dur_ps / 1000),
                            name, profile.module_name(str(module)), p))
    return {"ops": ops, "start_ns": int(start), "end_ns": int(start + dur),
            "devices": max(devices, 1), "route": sorted(route)}


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------


def reduce(ops, start_ns: int, end_ns: int, devices: int = 1,
           family: str | None = None) -> dict:
    """Device seconds per top-level scope (``scopes_s``, with an
    ``unscoped`` remainder) and per full scope path (``paths_s``), each
    averaged over the devices like ``profile.reduce``'s busy time.  Each
    instant of the window in which ops ran goes to the shortest op
    running then: a loop's body ops, not the ``while`` around them (ties
    to the one that started first), so the scopes add up to the busy
    time.
    ``family`` (a regular expression over module names) also gives the
    busy and unscoped seconds of its modules (``family_s``,
    ``family_unscoped_s``)."""
    fam = re.compile(family) if family else None
    scopes: dict[str, float] = {}
    paths: dict[str, float] = {}
    fam_s = fam_unscoped = 0.0
    by_dev: dict = {}
    for s, d, dev, module, path in ops:
        lo, hi = max(s, start_ns), min(s + d, end_ns)
        if hi > lo:
            by_dev.setdefault(dev, []).append((lo, hi, d, module, path))
    for evs in by_dev.values():
        evs.sort()
        bounds = sorted({x for e in evs for x in e[:2]})
        active, i = [], 0
        for lo, hi in zip(bounds, bounds[1:]):
            while i < len(evs) and evs[i][0] <= lo:
                heapq.heappush(active, (evs[i][2], i))
                i += 1
            while active and evs[active[0][1]][1] <= lo:
                heapq.heappop(active)
            if not active:
                continue
            _, _, _, module, path = evs[active[0][1]]
            ns = hi - lo
            top = path[0] if path else UNSCOPED
            scopes[top] = scopes.get(top, 0.0) + ns
            key = "/".join(path) or UNSCOPED
            paths[key] = paths.get(key, 0.0) + ns
            if fam is not None and fam.search(module):
                fam_s += ns
                if not path:
                    fam_unscoped += ns
    n = max(devices, 1) * 1e9
    return {"scopes_s": {k: v / n for k, v in scopes.items()},
            "paths_s": {k: v / n for k, v in paths.items()},
            "family_s": fam_s / n, "family_unscoped_s": fam_unscoped / n}


# ---------------------------------------------------------------------------
# For the metric readers
# ---------------------------------------------------------------------------


def profile_file(ctx) -> str | None:
    """The traced run's ``.xplane.pb``: ``bench/run.py`` profiles into
    ``<bench>/.work/<workload>/profile`` and removes it after the run."""
    d = os.path.join(ctx.cell.base, ".work", ctx.cell.workload["name"],
                     "profile")
    files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def scoped(ctx) -> dict | None:
    """The scope reduction of the traced run's profile, once per run
    (kept on the context); None without a profile or where no op ran
    under a scope.  Prints seconds per full scope path on stderr."""
    if "_scopes" in vars(ctx):
        return vars(ctx)["_scopes"]
    r = None
    path = profile_file(ctx) if ctx.profile is not None else None
    if path is not None:
        try:
            tr = from_xplane(path)
        except (OSError, ValueError) as exc:
            log(f"scopes: {type(exc).__name__}: {exc}")
            tr = None
        if tr is not None:
            fams = ctx.cell.mix.get("trace", {}).get("families", {})
            r = reduce(tr["ops"], tr["start_ns"], tr["end_ns"],
                       tr["devices"], fams.get("reconstruct"))
            answered = len(ctx.answered(*ctx.profiled))
            log(f"scopes (device s in the profiled window, {answered} "
                f"requests answered in it, paths from "
                f"{'+'.join(tr['route']) or 'nowhere'}): "
                + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
                    r["paths_s"].items(), key=lambda x: -x[1])))
            log(f"scopes: reconstruct family {r['family_s']:.6f} s, of "
                f"it unscoped {r['family_unscoped_s']:.6f} s")
            if set(r["scopes_s"]) <= {UNSCOPED}:
                r = None
    vars(ctx)["_scopes"] = r
    return r


def scope_ms_per_answer(ctx, scope: str):
    """Device seconds under one top-level scope in the profiled window
    per request answered inside it, in ms; None where nothing ran under
    it or nothing was answered."""
    r = scoped(ctx)
    if r is None:
        return None
    secs = r["scopes_s"].get(scope)
    answered = len(ctx.answered(*ctx.profiled))
    if not secs or not answered:
        return None
    return secs / answered * 1e3
