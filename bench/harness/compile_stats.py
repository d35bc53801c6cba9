"""Backend compiles and persistent-cache hits, from JAX's monitoring
events: how many programs a phase had to compile or load, and which."""
from __future__ import annotations


class CompileStats:
    """Process-wide once installed; read ``snapshot()`` around a phase."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.programs: list[tuple[str, float]] = []   # (name, seconds)

    def install(self) -> "CompileStats":
        import jax

        def on_duration(event, secs, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1
                self.programs.append((fun_name, round(secs, 3)))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}

    def named(self, before: dict, after: dict) -> list[tuple[str, float]]:
        """(program, seconds) of each compile or load between two
        snapshots."""
        return self.programs[before["compiles"]:after["compiles"]]
