"""The profiler round a part of the window, for ``--trace 1`` runs.

Only the process that holds the chip can trace it, and that is this one.
The Python tracer is off (it would record every call of the server's
threads); ``bench:`` annotations and the device planes are what is read.
The trace goes under ``TMPDIR`` and is deleted once reduced."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Any, Optional

from benchmark.lib import xplane


class Tracer:
    def __init__(self, enabled: bool, trace_seconds: float) -> None:
        self.enabled = enabled
        self.trace_seconds = trace_seconds
        self.t0: Optional[float] = None      # perf_counter
        self.t1: Optional[float] = None
        self._dir: Optional[str] = None
        self._window: Any = None

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION)
        self._window.__enter__()
        self.t0 = time.perf_counter()

    def tick(self, _elapsed: float) -> None:
        if self.t0 is not None and self.t1 is None \
                and time.perf_counter() - self.t0 >= self.trace_seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> Optional[xplane.TraceSummary]:
        if self._dir is None:
            return None
        try:
            found = glob.glob(os.path.join(self._dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return xplane.summarize(found[0], window_s=self.t1 - self.t0)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
