import time as _time

_import_t0 = _time.perf_counter()  # the package's ``import`` record starts here

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.entry import init_inference
from deepspeed_tpu.inference import fleet, serving

__all__ = ["DeepSpeedInferenceConfig", "InferenceEngine", "init_inference",
           "fleet", "serving"]

from deepspeed_tpu.utils import trace as _trace  # noqa: E402

_trace.imported(__name__, _import_t0)
