"""Quickstart: the graph-delta system behind one front door.

``GraphSession`` (repro/api.py) is the single entry point: ingest,
point/diff/agg queries, time sweeps, snapshots, and (with ``path=``)
crash-safe durability.  The lower-level pieces it wraps — the store,
the reconstruction theorems — are shown at the end.

  PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import jax.numpy as jnp

from repro.api import GraphSession, Op, Query
from repro.compile_cache import enable_compile_cache
from repro.core import (ADD_EDGE, ADD_NODE, REM_EDGE, reconstruct_dense,
                        reconstruct_sequential)

enable_compile_cache()

root = tempfile.mkdtemp(prefix="quickstart_graph_")

# A tiny social network: alice(0), bob(1), carol(2).  path= makes the
# session durable: every acknowledged ingest is WAL'd before it
# returns, so a kill -9 anywhere below loses nothing acknowledged.
with GraphSession.open(root, n_cap=8) as s:
    s.ingest([
        Op(ADD_NODE, 0, 0, t=1),        # alice joins
        Op(ADD_NODE, 1, 1, t=1),        # bob joins
        Op(ADD_EDGE, 0, 1, t=2),        # they befriend
        Op(ADD_NODE, 2, 2, t=3),        # carol joins
        Op(ADD_EDGE, 1, 2, t=4),        # bob ↔ carol
        Op(REM_EDGE, 0, 1, t=5),        # alice unfriends bob
    ])

    # Historical queries: keyword form builds a validated Query (a bad
    # measure / negative stride / t past the watermark raise clearly)
    print("bob's degree at t=4:   ", int(s.query("degree", t=4, v=1)))
    print("edges at t=4:          ", int(s.query("num_edges", t=4)))
    print("alice's change [2,5]:  ",
          int(s.query("degree", kind="diff", t_k=2, t_l=5, v=0)))

    # ... or explicit Query objects, batched into one device program
    print("batched:", [int(r) for r in s.query_many([
        Query("point", "node", "degree", t_k=4, v=v) for v in range(3)])])

    # Whole evolution series as ONE program (not 4 point queries)
    print("edge count over (1..5]:",
          [int(x) for x in s.sweep("num_edges", t_lo=1, t_hi=5)])

    s.flush()   # checkpoint: next open is replay-free

# Reopen = crash recovery: manifest + mmap'd segments + WAL replay.
# Queries against the recovered state bit-match the original session.
with GraphSession.open(root) as s:
    assert int(s.query("degree", t=4, v=1)) == 2
    print("reopened durable session at watermark", s.watermark, "✓")

    # The paper machinery underneath (core/): the current snapshot and
    # the invertible interval delta suffice for any past state
    # (Theorem 1), backward or forward ...
    store = s.store
    d = store.delta()
    g4 = reconstruct_dense(store.current, d, store.t_cur, 4)   # backward
    g_now = reconstruct_dense(g4, d, 4, store.t_cur)           # forward
    assert bool(jnp.all(g_now.adj == store.current.adj))

    # ... and the paper-faithful sequential replay (Algorithms 1-2)
    # agrees with the vectorized last-writer-wins reconstruction:
    g4_seq = reconstruct_sequential(store.current, d, store.t_cur, 4)
    assert bool(jnp.all(g4_seq.adj == g4.adj))
    print("sequential replay == vectorized last-writer-wins ✓")
