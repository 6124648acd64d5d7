"""Observability: metrics registry, span tracing, exporters, hooks.

Behavioral model (SURVEY.md §6.1, §6.5): TF1 hooks (LoggingTensorHook,
StepCounterHook, SummarySaverHook — basic_session_run_hooks.py:169,:674,:793)
+ ``tf.summary``/TensorBoard, and ``tf.profiler.experimental``
(profiler_v2.py:81: start/stop, :169: start_server for remote capture).

TPU-native: metrics come off the compiled step at throttled intervals
(training.loop), get written via tensorboardX; traces come from
``jax.profiler`` into the same TensorBoard profile plugin.

On top of that sits the unified layer: ``obs.metrics`` (thread-safe
Counter/Gauge/Histogram registry every serve/train component reports
into), ``obs.trace`` (the one span recorder: the scheduler's, the train
loop's and each request's spans, on under ``--trace_out`` or any profiler
session → Chrome trace JSON and raw intervals), ``obs.exporters``
(Prometheus ``/metrics`` endpoint).  The log-line hooks below are thin
readers of the registry's stats-provider bridge.
"""

# metrics/trace/exporters are dependency-free (no imports back into the
# package) and must come first: the hook modules below pull in
# training.loop, which lazily reads obs.metrics.
from distributed_tensorflow_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
)
from distributed_tensorflow_tpu.obs.trace import Tracer, default_tracer
from distributed_tensorflow_tpu.obs.lifecycle import (
    EMPTY_LIFECYCLE_STATS,
    LifecycleRecorder,
)
from distributed_tensorflow_tpu.obs.exporters import (
    MetricsServer,
    render_prometheus,
    write_chrome_trace,
)
from distributed_tensorflow_tpu.obs.tensorboard import (
    MetricsFileWriter,
    TensorBoardHook,
)
from distributed_tensorflow_tpu.obs.prefetch import PrefetchMonitorHook
from distributed_tensorflow_tpu.obs.profiling import (
    Profile,
    start_profiler_server,
)
from distributed_tensorflow_tpu.obs.serve import ServeMonitorHook

__all__ = [
    "Counter",
    "EMPTY_LIFECYCLE_STATS",
    "Gauge",
    "Histogram",
    "LifecycleRecorder",
    "MetricsFileWriter",
    "MetricsServer",
    "PrefetchMonitorHook",
    "Profile",
    "Registry",
    "ServeMonitorHook",
    "TensorBoardHook",
    "Tracer",
    "default_registry",
    "default_tracer",
    "render_prometheus",
    "start_profiler_server",
    "write_chrome_trace",
]
