from deepspeed_tpu.utils.logging import logger, log_dist, LoggerFactory
from deepspeed_tpu.utils.memory import OnDevice, see_memory_usage
from deepspeed_tpu.utils.tensor_fragment import (safe_get_full_fp32_param, safe_get_full_grad,
                                                 safe_get_full_optimizer_state,
                                                 safe_set_full_fp32_param)
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer, NoopTimer
from deepspeed_tpu.utils.tree import keypath_parts, keypath_str
