"""spark-rapids-tpu: a TPU-native accelerator with the capabilities of the
RAPIDS Accelerator for Apache Spark (reference: NVIDIA spark-rapids), built
on JAX/XLA/Pallas over Arrow-layout HBM batches instead of cuDF/CUDA.

Enable 64-bit mode up front: SQL engines are bigint/double-centric and Spark
semantics require true int64/float64 — jax defaults to 32-bit otherwise.

Importing the package (or any module of it) initialises NO JAX backend: a
chip belongs to one process, and clients, routers and tooling import the
plan-builder surface without taking it (tests/test_import_is_device_free.py).
"""

import jax

jax.config.update("jax_enable_x64", True)

from . import types  # noqa: E402,F401
from .batch import ColumnarBatch, DeviceColumn, Field, Schema  # noqa: E402,F401
from .config import RapidsTpuConf  # noqa: E402,F401

__version__ = "26.08.0"
