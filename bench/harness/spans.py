"""Reductions over the program's span ring and the reduced device trace,
shared by the per-layer metric readers."""
from __future__ import annotations


def self_times(spans, name: str) -> list[float]:
    """Self time (µs) of each span called ``name``: its duration less
    what nested spans of the same thread cover."""
    by_tid: dict = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    out = []
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        for i, e in enumerate(evs):
            if e["name"] != name:
                continue
            end = e["ts"] + e["dur"]
            covered, reach = 0.0, e["ts"]
            for c in evs[i + 1:]:
                if c["ts"] >= end:
                    break
                c_end = min(c["ts"] + c["dur"], end)
                if c_end > reach:          # direct children only
                    covered += c_end - max(c["ts"], reach)
                    reach = c_end
            out.append(e["dur"] - covered)
    return out


def per_call_ms(spans, name: str, self_time: bool = False):
    """Summed time of ``name`` spans per engine call (``query`` span),
    in ms; None without spans or calls."""
    if not spans:
        return None
    calls = sum(e["name"] == "query" for e in spans)
    if not calls:
        return None
    durs = (self_times(spans, name) if self_time
            else [e["dur"] for e in spans if e["name"] == name])
    return sum(durs) / calls / 1e3


def family_ms_per_answer(ctx, family: str):
    """Device seconds of one program family in the profiled window per
    request answered inside it, in ms; None where the family never ran
    or nothing was answered."""
    if ctx.profile is None or ctx.profiled is None:
        return None
    secs = ctx.profile["families_s"].get(family)
    answered = len(ctx.answered(*ctx.profiled))
    if not secs or not answered:
        return None
    return secs / answered * 1e3
