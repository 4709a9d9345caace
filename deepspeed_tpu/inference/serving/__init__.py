"""graft-serve: continuous in-flight batching with chunked prefill and
speculative decoding (ISSUE 14 / ROADMAP item 1)."""

from deepspeed_tpu.inference.serving.blocks import BlockPool
from deepspeed_tpu.inference.serving.events import (SERVE_EVENT_SCHEMAS,
                                                    iter_serve_events,
                                                    last_tick_signals,
                                                    validate_event)
from deepspeed_tpu.inference.serving.config import (ENV_PREFIX_CACHE,
                                                    ENV_WEIGHT_DTYPE,
                                                    ServingConfig,
                                                    SpeculationConfig,
                                                    resolve_intended_prefix_cache,
                                                    resolve_intended_weight_dtype,
                                                    resolve_prefix_cache,
                                                    resolve_weight_dtype,
                                                    set_default_prefix_cache,
                                                    set_default_weight_dtype)
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
    "ENV_PREFIX_CACHE", "ENV_WEIGHT_DTYPE", "MIGRATABLE_STATES",
    "MigrationError", "Request",
    "RequestQueue", "SERVE_EVENT_SCHEMAS", "ServingConfig",
    "SpeculationConfig", "iter_serve_events", "last_tick_signals",
    "make_slot_cache",
    "resolve_intended_prefix_cache",
    "resolve_intended_weight_dtype",
    "resolve_prefix_cache", "resolve_weight_dtype",
    "serve_programs",
    "set_default_prefix_cache",
    "set_default_weight_dtype", "slot_capacity",
    "validate_event",
]
