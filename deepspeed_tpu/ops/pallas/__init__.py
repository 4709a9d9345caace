"""Pallas TPU kernels — the TPU-native replacement for the reference's
CUDA kernel library (``csrc/``). Each kernel has an XLA reference twin used
in parity tests; on CPU the kernels run in Pallas interpret mode."""
import time as _time

_import_t0 = _time.perf_counter()  # the package's ``import`` record starts here

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from deepspeed_tpu.utils import trace as _trace  # noqa: E402

# (imported at first use, often inside a model's trace: ``jax.experimental.pallas`` is most of it)
_trace.imported(__name__, _import_t0)
