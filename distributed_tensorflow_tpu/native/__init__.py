"""Native (C++) host runtime components.

The reference's native layer is TensorFlow's C++ runtime (gRPC server,
collective executor, tf.data kernels — SURVEY.md §2 L1-L4).  On TPU the
device-side equivalents collapse into XLA; what legitimately stays native is
*host* work on the input path.  ``dtt_loader`` is that piece: a mmap +
threaded shuffle/batch/prefetch loader compiled from
``dtt_loader.cpp`` and bound via ctypes (no pybind11 in this environment).
"""

from distributed_tensorflow_tpu.native.loader import (
    NativeRecordLoader,
    RecordFile,
    RecordSetLoader,
    make_record_loader,
    native_available,
    reader_name,
)

__all__ = [
    "NativeRecordLoader",
    "RecordFile",
    "RecordSetLoader",
    "make_record_loader",
    "native_available",
    "reader_name",
]
