"""What the benchmark observes of the program while it runs.

``CompileLog`` listens to JAX's monitoring events: when each program
finished compiling or loading from the persistent cache, and how many
seconds of real compilation (cache misses) the run paid.

``StepRecorder`` keeps references to the state that the training loop's
own jitted step takes and returns in its first calls: the parameters
before step 0, the optimizer state after step 1 and the parameters
after step 3.  It wraps ``jax.jit`` as seen from the loop's module for
the duration of one call, so the step program is the one the loop
builds; nothing is copied or synchronised while the window runs.
It relies on the step taking ``(params, opt_state, ...)`` and returning
``(params, opt_state, loss)``; where it finds another shape it records
nothing, and the run is reported as not correct.
"""
from __future__ import annotations

import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Backend compiles and cache loads, with the host time each ended."""

    def __init__(self, jax):
        self.events: list = []   # (end perf_counter, fun_name, secs, compiled)
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self._hit = True

    def _duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.events.append(
                (time.perf_counter(), kw.get("fun_name", "?"), secs, not self._hit))
            self._hit = False

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> list:
        return self.events[mark:]

    def compile_s(self, mark: int = 0) -> float:
        """Seconds of real compilation since ``mark`` (cache loads: 0)."""
        return sum(e[2] for e in self.events[mark:] if e[3])

    def summary(self) -> str:
        built = [e for e in self.events if e[3]]
        return (f"{len(self.events) - len(built)} loaded from the cache, "
                f"{len(built)} compiled in {sum(e[2] for e in built):.3f} s"
                + (f" ({', '.join(sorted({e[1] for e in built}))})" if built else ""))

    def ready_at(self, mark: int, name: str = "train_step"):
        """Host time at which the step program of a call was ready: the
        end of the last compile or cache load named ``name`` since
        ``mark``, else of the last one at all."""
        evs = self.events[mark:]
        named = [e for e in evs if name in e[1]]
        pick = (named or evs)
        return pick[-1][0] if pick else None


class StepRecorder:
    """Holds the step state of the first calls of the training loop's step."""

    def __init__(self, loop_module, keep_args: bool = False):
        self.module = loop_module
        self.keep_args = keep_args
        self.calls = 0
        self.p0 = self.opt1 = self.p3 = None
        self.step_fn = self.args0 = None
        self.unrecognised = None

    def hlo(self) -> str:
        """Optimized HLO of the step program (``keep_args=True``): lowered
        again from the first call's arguments; the compile is a cache
        load."""
        return self.step_fn.lower(*self.args0).compile().as_text()

    def _seen(self, args, out):
        i = self.calls
        self.calls += 1
        if i > 2:
            return
        if not (isinstance(out, tuple) and len(out) == 3 and len(args) >= 2
                and hasattr(out[1], "mu")):
            self.unrecognised = (f"step call {i} returned "
                                 f"{type(out).__name__}, not (params, opt, loss)")
            return
        if i == 0:
            self.p0, self.opt1 = args[0], out[1]
        elif i == 2:
            self.p3 = out[0]

    def __enter__(self):
        real = self.module.jax
        rec = self

        def jit(fn=None, **kw):
            if fn is None:
                return lambda f: jit(f, **kw)
            compiled = real.jit(fn, **kw)

            def call(*args, **kwargs):
                if rec.keep_args and rec.args0 is None:
                    rec.step_fn, rec.args0 = compiled, args
                out = compiled(*args, **kwargs)
                if rec.calls < 3:
                    rec._seen(args, out)
                else:
                    rec.calls += 1
                return out

            return call

        class _Jax:
            def __getattr__(self, name):
                return jit if name == "jit" else getattr(real, name)

        self._real = real
        self.module.jax = _Jax()
        return self

    def __exit__(self, *exc):
        self.module.jax = self._real
        return False
