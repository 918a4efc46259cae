"""Counts XLA compilations (or persistent-cache loads of a program) so a
run can show that nothing compiled inside its measured window."""

import jax

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """``count`` grows by one for every backend compile event; registered
    once per process (jax has no public way to remove a listener)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == _EVENT:
            self.count += 1
            self.seconds += duration
