"""graft-serve: continuous in-flight batching with chunked prefill and
speculative decoding (ISSUE 14 / ROADMAP item 1)."""
import time as _time

_import_t0 = _time.perf_counter()  # the package's ``import`` record starts here

from deepspeed_tpu.inference.serving.blocks import BlockPool
from deepspeed_tpu.inference.serving.events import (SERVE_EVENT_SCHEMAS,
                                                    iter_serve_events,
                                                    last_tick_signals,
                                                    validate_event)
from deepspeed_tpu.inference.serving.config import (ServingConfig,
                                                    SpeculationConfig)
from deepspeed_tpu.inference.serving.programs import (make_slot_cache,
                                                      serve_programs,
                                                      slot_capacity)
from deepspeed_tpu.inference.serving.queue import RequestQueue
from deepspeed_tpu.inference.serving.request import (ACTIVE, FINISHED, PREFILL,
                                                     QUEUED, REFUSED, Request)
from deepspeed_tpu.inference.serving.scheduler import (MIGRATABLE_STATES,
                                                       ContinuousBatchingScheduler,
                                                       MigrationError)

__all__ = [
    "ACTIVE", "FINISHED", "PREFILL", "QUEUED", "REFUSED",
    "BlockPool", "ContinuousBatchingScheduler",
    "MIGRATABLE_STATES",
    "MigrationError", "Request",
    "RequestQueue", "SERVE_EVENT_SCHEMAS", "ServingConfig",
    "SpeculationConfig", "iter_serve_events", "last_tick_signals",
    "make_slot_cache", "serve_programs", "slot_capacity",
    "validate_event",
]

from deepspeed_tpu.utils import trace as _trace  # noqa: E402

_trace.imported(__name__, _import_t0)
