"""The profiler window of a ``--trace 1`` run."""

from __future__ import annotations

import shutil
from pathlib import Path
from typing import Optional

from .trace import WINDOW_SPAN


class Window:
    """Starts the device trace and opens the ``bench.window`` host span;
    ``stop`` closes both. With no directory it does nothing."""

    def __init__(self, trace_dir: Optional[Path]):
        self.dir = trace_dir
        self.active = False
        self._span = None

    def start(self) -> None:
        if self.dir is None:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans only, no Python calls
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
