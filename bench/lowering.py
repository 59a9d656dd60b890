"""What JAX compiled: compile time and the Pallas kernels of a program.

Copied from the repository's chip smoke, not imported, so that a change to
the smoke cannot move the yardstick.
"""
from __future__ import annotations

import base64
import re


class CompileClock:
    """Counts and sums JAX's own trace, lower and backend-compile events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs
            self.events += 1

    def lap(self) -> tuple[float, int]:
        """(seconds, events) since the last lap."""
        out = (self.secs, self.events)
        self.secs, self.events = 0.0, 0
        return out


class Recorder:
    """Stands in for a jitted function and keeps the arguments of its last
    call, so that the program a call ran can be lowered again as it was
    called: the same function, the same static and traced arguments."""

    def __init__(self, fn):
        self.fn = fn
        self.args = None

    def __call__(self, *args, **kwargs):
        self.args = (args, kwargs)
        return self.fn(*args, **kwargs)

    def lower(self):
        if self.args is None:
            raise RuntimeError("the recorded program was never called")
        args, kwargs = self.args
        return self.fn.lower(*args, **kwargs)


def tpu_kernels(lowered) -> list[str]:
    """Pallas kernel names in the ``tpu_custom_call``s of a lowered program.

    The kernel's Mosaic module travels base64-encoded in the custom call's
    backend config; its symbol table carries the kernel function's name.
    """
    names = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered.as_text()):
        names += [m.decode() for m in
                  re.findall(rb"_\w*kernel", base64.b64decode(body))]
    return sorted(set(names))


def footprint(compiled) -> int | None:
    """Bytes a compiled program holds on the device while it runs:
    arguments, results, temporaries and generated code, less the results
    that alias arguments.  The allocator's peak does not see the
    temporaries XLA reserves for an execution."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes + m.generated_code_size_in_bytes
               - m.alias_size_in_bytes)
