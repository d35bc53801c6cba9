"""End-to-end smoke of the temporal graph store on TPU chips.

    python chip_smoke.py [--seed S]            # one chip: edge + dense phase
    python chip_smoke.py --chips 4 [--seed S]  # four chips: sharded vs one

Drives the system the way a user does, through ``repro.api.GraphSession``:
ingest a seeded power-law history (``core.generate.generate_ops``),
flush, answer a batch of point / diff / agg queries and one sweep, ingest
the rest of the history, flush again (an epoch swap) and query again.

* edge phase: ``layout="edge"`` at N = 2^19 nodes, ~3M ops (E/N ≈ 8,
  the edge-scaling benchmark's shape);
* dense phase: ``layout="dense"`` at N = 4096, adding ``triangles`` and
  ``degree_distribution``;
* ``--chips 4``: only the multi-chip path — the same sessions on a
  four-device mesh with ``shard="force"`` (slot, row and batch sharded
  groups), compared bit for bit with one device in the same process.

Every answer is compared exactly with ``Reference``, a plain numpy replay
of the op log that shares no code with ``src/repro/core``.  A mismatch or
a failed phase exits non-zero.  Without a TPU the script exits non-zero
before any phase.  The last line of a good run is one JSON object naming
the device.  The compile cache goes where ``repro.compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# (layout, n_cap, n_nodes) per phase; n_cap divides by 4 for the mesh.
EDGE = ("edge", 2 ** 19, 2 ** 19)
DENSE = ("dense", 4096, 4096)

# Op codes of the log (the store's wire format, ``core.delta``).
ADD_NODE, REM_NODE, ADD_EDGE, REM_EDGE = 0, 1, 2, 3
# Bins of the degree_distribution measure: degrees 0..64, the last bin
# collecting everything above.
DEGREE_BINS = 64


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------


def history(n_nodes: int, seed: int):
    """The seeded evolving power-law op stream, as a list of ``Op`` and
    as numpy columns (op, u, v, t)."""
    from repro.core.generate import EvolutionParams, generate_ops
    # the edge-scaling benchmark's shape: 4 preferential edges per
    # arrival (E/N ≈ 8), ~2 events per arrival, ~512 time units
    params = EvolutionParams(m_attach=4, lam_extra=0.5, lam_remove=0.5,
                             events_per_unit=max(8, n_nodes // 256))
    ops = generate_ops(n_nodes, params, seed=seed)
    cols = np.array([(o.op, o.u, o.v, o.t) for o in ops], np.int64).T
    return ops, cols


class Reference:
    """The graph at time t, replayed with numpy from the op log: per
    node and per edge key, the last op at or before t decides."""

    def __init__(self, cols: np.ndarray, n_cap: int):
        op, u, v, t = cols
        self.op, self.t, self.n_cap = op, t, n_cap
        edge = op >= ADD_EDGE
        key = np.minimum(u, v) * n_cap + np.maximum(u, v)
        keys, kid = np.unique(key[edge], return_inverse=True)
        self.ku, self.kv = keys // n_cap, keys % n_cap
        self.k = len(keys)
        # item per op: its edge key, or K + node id for node ops
        self.item = np.empty(len(op), np.int64)
        self.item[edge] = kid
        self.item[~edge] = self.k + u[~edge]
        self._inc: dict[int, np.ndarray] = {}
        # largest degree seen by a degree_distribution answer
        self.max_degree: int | None = None

    def incident(self, v: int) -> np.ndarray:
        if v not in self._inc:
            self._inc[v] = np.flatnonzero((self.ku == v) | (self.kv == v))
        return self._inc[v]

    def measures(self, needs: dict) -> dict:
        """``needs`` maps time -> set of (measure, v); returns
        (t, measure, v) -> value, replaying the log once in time order."""
        last = np.full(self.k + self.n_cap, -1, np.int64)
        lo, out = 0, {}
        for t in sorted(needs):
            hi = int(np.searchsorted(self.t, t, side="right"))
            np.maximum.at(last, self.item[lo:hi], np.arange(lo, hi))
            lo = hi
            alive = (last >= 0) & np.isin(self.op[np.maximum(last, 0)],
                                          (ADD_NODE, ADD_EDGE))
            edges, nodes = alive[:self.k], alive[self.k:]
            for measure, v in needs[t]:
                out[t, measure, v] = self._measure(measure, v, edges, nodes)
        return out

    def _measure(self, measure, v, edges, nodes):
        if measure == "degree":
            return int(edges[self.incident(v)].sum())
        n_e, n_n = int(edges.sum()), int(nodes.sum())
        if measure == "num_edges":
            return n_e
        if measure == "num_nodes":
            return n_n
        if measure == "avg_degree":
            return np.float32(2.0) * np.float32(n_e) / np.float32(max(n_n, 1))
        ku, kv = self.ku[edges], self.kv[edges]
        if measure == "degree_distribution":
            deg = (np.bincount(ku, minlength=self.n_cap)
                   + np.bincount(kv, minlength=self.n_cap))
            self.max_degree = max(self.max_degree or 0, int(deg.max()))
            return np.bincount(np.minimum(deg, DEGREE_BINS), weights=nodes,
                               minlength=DEGREE_BINS + 1).astype(np.int64)
        if measure == "triangles":
            adj = np.zeros((self.n_cap, self.n_cap), bool)
            adj[ku, kv] = adj[kv, ku] = True
            rows = np.packbits(adj, axis=1)
            common = np.unpackbits(rows[ku] & rows[kv], axis=1).sum()
            return int(common) // 3
        raise ValueError(f"no reference for {measure!r}")

    def answers(self, queries) -> list:
        """Reference answer per ``Query`` (sweeps are ``evolve`` ones)."""
        needs: dict = {}

        def need(t, measure, v):
            needs.setdefault(int(t), set()).add((measure, v))

        for q in queries:
            for t in _times(q):
                need(t, q.measure, q.v)
        vals = self.measures(needs)
        out = []
        for q in queries:
            series = [vals[t, q.measure, q.v] for t in _times(q)]
            if q.kind == "point":
                out.append(series[0])
            elif q.kind == "diff":
                out.append(abs(series[-1] - series[0]))
            elif q.kind == "evolve":
                out.append(np.asarray(series))
            elif q.agg == "mean":
                # the measure's exact f32 sum of integers over the width
                out.append(np.float32(sum(series)) / np.float32(len(series)))
            else:
                out.append(min(series) if q.agg == "min" else max(series))
        return out


def _times(q) -> list[int]:
    if q.kind == "point":
        return [q.t_k]
    if q.kind == "diff":
        return [q.t_k, q.t_l]
    return list(range(q.t_k, q.t_l + 1, q.stride))


def make_queries(rng, t_lo: int, t_hi: int, n_nodes: int, dense: bool):
    """64 queries over [t_lo, t_hi] (72 on the dense layout) and one
    sweep: point / diff / agg on node degree and global num_edges /
    avg_degree.  Global aggregates use min/max: their f32 mean would
    depend on the summation order, which a reference cannot fix."""
    from repro.api import Query

    def t():
        return int(rng.integers(t_lo, t_hi + 1))

    def span(width):
        a = int(rng.integers(t_lo, t_hi + 1))
        return a, min(t_hi, a + int(rng.integers(0, width)))

    def node():
        hub = rng.random() < 0.5          # low ids are the hubs
        return int(rng.integers(0, 64 if hub else n_nodes))

    qs = [Query("point", "node", "degree", t_k=t(), v=node())
          for _ in range(16)]
    for _ in range(8):
        a, b = span(64)
        qs.append(Query("diff", "node", "degree", t_k=a, t_l=b, v=node()))
    for i in range(8):
        a, b = span(16)
        qs.append(Query("agg", "node", "degree", t_k=a, t_l=b, v=node(),
                        agg=("mean", "min", "max")[i % 3]))
    for measure in ("num_edges", "avg_degree"):
        qs += [Query("point", "global", measure, t_k=t()) for _ in range(8)]
        for _ in range(4):
            a, b = span(64)
            qs.append(Query("diff", "global", measure, t_k=a, t_l=b))
    for i in range(8):
        a, b = span(8)
        qs.append(Query("agg", "global", "num_edges", t_k=a, t_l=b,
                        agg=("min", "max")[i % 2]))
    if dense:
        qs += [Query("point", "global", m, t_k=t())
               for m in ("triangles", "degree_distribution") for _ in range(4)]
    stride = max(1, (t_hi - t_lo) // 16)
    sweep = Query("evolve", "global", "num_edges", t_k=t_lo, t_l=t_hi,
                  stride=stride)
    return qs, sweep


# ---------------------------------------------------------------------------
# Running and checking
# ---------------------------------------------------------------------------


def ask(session, queries, sweep, **evaluate_kw) -> list:
    """Answers from a session: ``query_many`` plus ``sweep``, or (with
    ``evaluate_kw``) one batch through the serving layer directly."""
    if evaluate_kw:
        return session.live.evaluate_many(list(queries) + [sweep],
                                          stale="block", **evaluate_kw)
    got = session.query_many(queries)
    got.append(session.sweep(sweep.measure, sweep.t_k, sweep.t_l,
                             stride=sweep.stride))
    return got


def mismatches(label: str, queries, got, want) -> list[str]:
    bad = []
    for q, g, w in zip(queries, got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or not np.array_equal(g.astype(np.float64),
                                                    w.astype(np.float64)):
            bad.append(f"{label}: {q} -> {g.tolist()} expected {w.tolist()}")
    return bad


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (process-wide once installed)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileStats":
        import jax

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses}


def device_bytes(devices) -> list[dict]:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({k: st.get(k) for k in ("bytes_in_use",
                                           "peak_bytes_in_use",
                                           "bytes_limit")})
    return out


def _split(ops, cols):
    """History split at 3/4 of its time span: ingested before the first
    flush, and after it (the second flush is then an epoch swap)."""
    t_split = int(cols[3].max()) * 3 // 4
    k = int(np.searchsorted(cols[3], t_split, side="right"))
    return t_split, ops[:k], ops[k:]


def run_phase(layout: str, n_cap: int, n_nodes: int, seed: int,
              devices=None, stats: CompileStats | None = None) -> dict:
    """One single-device phase; returns its report, whose ``mismatches``
    list is empty when every answer equals the reference."""
    from repro.api import GraphSession
    from repro.obs.metrics import MetricsRegistry
    report = {"phase": layout, "n_cap": n_cap}
    clock = time.perf_counter()

    def lap(name):
        nonlocal clock
        now = time.perf_counter()
        report[name + "_s"] = now - clock
        clock = now

    ops, cols = history(n_nodes, seed)
    ref = Reference(cols, n_cap)
    t_split, first, rest = _split(ops, cols)
    report.update(ops=len(ops), edge_keys=ref.k, t_max=int(cols[3].max()))
    lap("generate")
    rng = np.random.default_rng(seed)
    before = stats.snapshot() if stats is not None else None
    bad: list[str] = []
    with GraphSession.open(None, n_cap=n_cap, layout=layout,
                           metrics=MetricsRegistry()) as s:
        lo, w1 = 1, None
        for batch, part in (("batch1", first), ("batch2", rest)):
            s.ingest(part)
            s.flush()
            lap("ingest" + batch[-1])
            if w1 is not None:         # straddle the previous watermark
                lo = max(1, 2 * w1 - s.watermark)
            w1 = s.watermark
            qs, sweep = make_queries(rng, lo, s.watermark, n_nodes,
                                     layout == "dense")
            got = ask(s, qs, sweep)
            lap("query" + batch[-1])
            bad += mismatches(batch, qs + [sweep], got,
                              ref.answers(qs + [sweep]))
            lap("reference" + batch[-1])
        eng = s.live.engine
        report.update(queries=2 * (len(qs) + 1), epochs=s.live.epoch,
                      t_split=t_split, watermark=s.watermark,
                      e_cap=(eng.current_edge.e_cap
                             if eng.current_edge is not None else None),
                      delta_cap=eng.delta.capacity,
                      max_degree=ref.max_degree,
                      groups=_groups_by(s, "plan", "layout"))
        if devices is not None:
            report["device"] = device_bytes(devices)
    if stats is not None:
        after = stats.snapshot()
        report.update({k: after[k] - before[k] for k in after})
    report["mismatches"] = bad
    return report


def _groups_by(session, *labels: str) -> dict:
    """Dispatched device programs counted by the values of ``labels``
    (of ``engine_groups_total``: plan, layout, shard)."""
    groups = session.metrics()["counters"].get("engine_groups_total", {})
    out: dict = {}
    for key, n in groups.items():
        kv = dict(p.split("=") for p in key.split(","))
        name = "/".join(kv[k] for k in labels)
        out[name] = out.get(name, 0) + n
    return out


def run_sharded(layout: str, n_cap: int, n_nodes: int, seed: int,
                devices) -> dict:
    """The same steps on a one-device session and on a session over a
    mesh of ``devices`` with every shardable group sharded; answers must
    be bitwise equal to each other and to the reference."""
    from repro.api import GraphSession
    from repro.obs.metrics import MetricsRegistry
    from repro.sharding.graph import graph_mesh
    ops, cols = history(n_nodes, seed)
    ref = Reference(cols, n_cap)
    t_split, first, rest = _split(ops, cols)
    rng = np.random.default_rng(seed)
    one = GraphSession.open(None, n_cap=n_cap, layout=layout,
                            metrics=MetricsRegistry())
    many = GraphSession.open(None, n_cap=n_cap, layout=layout,
                             mesh=graph_mesh(devices),
                             metrics=MetricsRegistry())
    report = {"phase": f"{layout}@{len(devices)}", "n_cap": n_cap,
              "ops": len(ops)}
    bad: list[str] = []
    t0 = time.perf_counter()
    lo = 1
    for batch, part in (("batch1", first), ("batch2", rest)):
        for s in (one, many):
            s.ingest(part)
            s.flush()
        w = one.watermark
        qs, sweep = make_queries(rng, lo, w, n_nodes, layout == "dense")
        lo = max(1, w - (w - lo) // 2)
        want = ref.answers(qs + [sweep])
        got_one = ask(one, qs, sweep)
        got_many = ask(many, qs, sweep, shard="force")
        bad += mismatches(batch + "/one", qs + [sweep], got_one, want)
        bad += mismatches(batch + "/mesh", qs + [sweep], got_many, got_one)
        if layout == "dense":
            # the planner sends every row-decomposable measure to the slot
            # registry; forcing dense execution is what runs row sharding
            got_rows = ask(many, qs, sweep, layout="dense", shard="force")
            bad += mismatches(batch + "/rows", qs + [sweep], got_rows,
                              got_one)
    report.update(seconds=time.perf_counter() - t0,
                  shard_modes=_groups_by(many, "shard"),
                  device=device_bytes(devices), mismatches=bad)
    one.close()
    many.close()
    return report


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a four-chip mesh")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)

    # the TPU library otherwise writes its logs to a fixed directory
    # under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    stats = CompileStats().install()
    failed = 0
    for layout, n_cap, n_nodes in (EDGE, DENSE):
        if args.chips == 1:
            report = run_phase(layout, n_cap, n_nodes, args.seed,
                               devices=devices, stats=stats)
        else:
            report = run_sharded(layout, n_cap, n_nodes, args.seed, devices)
            modes = report["shard_modes"]
            report.update(stats.snapshot())
            needed = {"slots", "batch"} | ({"rows"} if layout == "dense"
                                           else set())
            if not needed <= set(modes):
                report["mismatches"].append(
                    f"shard modes {sorted(needed - set(modes))} never ran")
        bad = report["mismatches"]
        report["mismatches"] = len(bad)
        print(json.dumps(report), flush=True)
        for line in bad[:20]:
            print("MISMATCH", line, flush=True)
        failed += len(bad)
        gc.collect()
    if failed:
        print(f"chip_smoke: {failed} answers differ from the reference",
              file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
