"""Backend-compile seconds by jitted-function name and persistent-cache
hits and misses, from JAX's own monitoring events (copied from
``chip_smoke.py::CompileLog``)."""

from __future__ import annotations


class CompileLog:
    def __init__(self):
        import jax.monitoring as mon

        self.compiles: list[tuple[str, float]] = []
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name")), float(secs)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple[int, int, int]:
        return len(self.compiles), self.hits, self.misses

    def since(self, mark) -> dict:
        n, hits, misses = mark
        by_name: dict[str, list] = {}
        for name, secs in self.compiles[n:]:
            by_name.setdefault(name, []).append(round(secs, 2))
        slow = {k: v for k, v in by_name.items() if sum(v) >= 0.5}
        return {"programs": len(self.compiles) - n,
                "total_s": round(sum(s for _, s in self.compiles[n:]), 2),
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses,
                "half_second_or_more": slow}
