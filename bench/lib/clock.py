"""JAX's compile-duration events, summed and counted.

A copy of ``chip_smoke.py``'s ``CompileClock``: while registered it sums
the seconds JAX spends lowering to MLIR and compiling (or loading a
compiled program from the persistent cache), and counts those events, so
the harness can report set-up compilation and see any compilation inside a
measured window.
"""

from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __call__(self, event, duration, **kwargs):
        if event in EVENTS:
            self.seconds += duration
            self.events += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
