"""Capture of one traced window: jax's profiler around a part of the measured
window, with the python tracer off (it would slow the host it measures) and
a span named ``bench.window`` that marks the window on the trace's clock."""

from __future__ import annotations

import shutil
import tempfile

from benchmark import trace_reduce


def span(name: str):
    """A host span on the device trace's clock (``TraceAnnotation``); costs
    next to nothing while no trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class WindowTrace:
    """``start()`` and ``stop()`` on one thread; ``reduce()`` afterwards."""

    def __init__(self, spans: tuple = ()):
        self.spans = tuple(spans)
        self.dir: str | None = None
        self._window = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = span(trace_reduce.WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        try:
            raw = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce(raw, self.spans)
