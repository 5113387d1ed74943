"""Counts the programs JAX lowers, so that a run can show that nothing
compiles inside its measured window."""
from __future__ import annotations

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts lowerings (each new program, compiled or found in the cache)
    from ``start()`` on."""

    def __init__(self):
        self.count = 0
        self._on = False
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, _secs: float, **_kw) -> None:
        if self._on and event == LOWER_EVENT:
            self.count += 1

    def start(self) -> None:
        self.count, self._on = 0, True

    def stop(self) -> int:
        self._on = False
        return self.count
