"""Back-compat shim: the profiling stub grew into `glom_tpu/tracing/`.

Everything this module used to define lives there now — spans, the
step-windowed TraceCapture, HBM accounting, and the flight recorder are
the new surface (docs/OBSERVABILITY.md). The original names keep working
from here:

    trace / start_server / annotate  -> glom_tpu.tracing.capture
"""

from glom_tpu.tracing.capture import annotate, start_server, trace

__all__ = ["annotate", "start_server", "trace"]
