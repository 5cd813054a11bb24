"""Every XLA compile request this process makes, from ``jax.monitoring``
(copied from chip_smoke.py's listener, PR 21, which is sound). A
persistent-cache hit still counts as a request; its seconds are then the time
to load the program."""


class Compiles:
    def __init__(self):
        import jax

        self.requests = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return {"requests": self.requests, "seconds": self.seconds,
                "hits": self.hits, "misses": self.misses}


def since(before, after):
    return {k: after[k] - before[k] for k in after}
