"""graft-serve configuration: the ``"serving"`` config block.

Continuous in-flight batching (ISSUE 14 / ROADMAP item 1) is driven by a
small set of knobs with the same layered resolution discipline as the MoE
route and the attention geometry: explicit > env > config > default, with
the env layer (``DS_SERVE_WQ``, ``DS_SERVE_PREFIX_CACHE``) existing so the
graft-audit serving scenarios can catch a forced/leaked serving knob the
exact way ``DS_MOE_ROUTE=dense`` is caught — the traced program drifts,
the committed budget/signature does not, lint exits 1.
"""

import os
import threading
from typing import Optional, Tuple

from pydantic import Field, model_validator

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel

#: env override for the served weight dtype (graft-quant-serve); same
#: drift seam: a forced/leaked value changes the traced decode program,
#: the serve_quant_decode_step budget stays priced for the intent
ENV_WEIGHT_DTYPE = "DS_SERVE_WQ"

WEIGHT_DTYPE_CHOICES = ("fp", "int8", "int4")
DEFAULT_WEIGHT_DTYPE = "fp"

#: env override for content-hashed KV prefix caching (graft-prefix-cache);
#: the same drift seam — forcing it off under the env changes admission
#: depth and prefill skip behaviour while the committed intent (and the
#: serve_prefix_decode_step budget priced for it) stays put
ENV_PREFIX_CACHE = "DS_SERVE_PREFIX_CACHE"

PREFIX_CACHE_CHOICES = ("on", "off")
DEFAULT_PREFIX_CACHE = "on"

_lock = threading.Lock()
_config_weight_dtype: Optional[str] = None
_config_prefix_cache: Optional[str] = None


def _check(value: Optional[str], choices, what: str) -> Optional[str]:
    if value is not None and value not in choices:
        raise ValueError(f"unknown {what} {value!r}; choices: {list(choices)}")
    return value


def set_default_weight_dtype(mode: Optional[str]) -> None:
    """Install the scheduler-level served weight dtype (None clears)."""
    global _config_weight_dtype
    with _lock:
        _config_weight_dtype = _check(mode, WEIGHT_DTYPE_CHOICES, "weight_dtype")


def resolve_weight_dtype(mode: Optional[str] = None) -> Tuple[str, str]:
    """Resolve ``(mode, source)`` for the served weight dtype.

    ``fp`` (default) serves the param tree as stored; ``int8``/``int4``
    serve per-group quantized codes with dequant fused into the GEMM
    (``ops/pallas/quant_matmul.py``). ``source`` names the deciding layer
    (``explicit`` > ``env`` > ``config`` > ``default``), the same evidence
    convention as the MoE route's."""
    src, m = "default", DEFAULT_WEIGHT_DTYPE
    if _config_weight_dtype is not None:
        m, src = _config_weight_dtype, "config"
    env = os.environ.get(ENV_WEIGHT_DTYPE, "").strip() or None
    if env is not None:
        m, src = _check(env, WEIGHT_DTYPE_CHOICES,
                        f"weight_dtype (from {ENV_WEIGHT_DTYPE})"), "env"
    if mode is not None:
        m, src = _check(mode, WEIGHT_DTYPE_CHOICES, "weight_dtype"), "explicit"
    return m, src


def resolve_intended_weight_dtype(mode: Optional[str] = None) -> str:
    """The weight dtype the *committed configuration* intends, skipping
    the env layer — what ``serve_quant_decode_step`` prices its budget
    and collective signature for (mirror of
    ``moe.routing.resolve_intended_route``)."""
    if mode is not None:
        return _check(mode, WEIGHT_DTYPE_CHOICES, "weight_dtype")
    if _config_weight_dtype is not None:
        return _config_weight_dtype
    return DEFAULT_WEIGHT_DTYPE


def set_default_prefix_cache(mode: Optional[str]) -> None:
    """Install the scheduler-level prefix-cache default (None clears)."""
    global _config_prefix_cache
    with _lock:
        _config_prefix_cache = _check(mode, PREFIX_CACHE_CHOICES, "prefix_cache")


def resolve_prefix_cache(mode: Optional[str] = None) -> Tuple[str, str]:
    """Resolve ``(mode, source)`` for content-hashed KV prefix caching.

    ``on`` (default) ref-counts and content-addresses the BlockPool:
    committed full blocks index under a rolling hash, freed blocks with a
    live hash park on a cached-free LRU, and new prompts prefill only
    their uncached tail. ``off`` restores the private-blocks pool (parity
    debugging / the A/B control arm). ``source`` names the deciding layer
    (``explicit`` > ``env`` > ``config`` > ``default``), the same
    evidence convention as :func:`resolve_weight_dtype`."""
    src, m = "default", DEFAULT_PREFIX_CACHE
    if _config_prefix_cache is not None:
        m, src = _config_prefix_cache, "config"
    env = os.environ.get(ENV_PREFIX_CACHE, "").strip() or None
    if env is not None:
        m, src = _check(env, PREFIX_CACHE_CHOICES,
                        f"prefix_cache (from {ENV_PREFIX_CACHE})"), "env"
    if mode is not None:
        m, src = _check(mode, PREFIX_CACHE_CHOICES, "prefix_cache"), "explicit"
    return m, src


def resolve_intended_prefix_cache(mode: Optional[str] = None) -> str:
    """The prefix-cache mode the *committed configuration* intends,
    skipping the env layer — what ``serve_prefix_decode_step`` stamps in
    its metadata so a forced/leaked ``DS_SERVE_PREFIX_CACHE`` drifts the
    traced evidence away from the committed intent (R013 catches it)."""
    if mode is not None:
        return _check(mode, PREFIX_CACHE_CHOICES, "prefix_cache")
    if _config_prefix_cache is not None:
        return _config_prefix_cache
    return DEFAULT_PREFIX_CACHE


class SpeculationConfig(DeepSpeedConfigModel):
    """Speculative decoding knobs. The drafter is the compression/KD
    student (``compression/compress.py`` ``student_initialization`` seeds
    it from the target's layers); verification is batched on the target
    and lossless under greedy decoding: a rejected draft position is
    replaced by the target's own argmax token."""

    enabled: bool = False
    #: draft tokens per speculation round (the verify block is k+1 wide:
    #: the last accepted token rides along so the target also produces
    #: the bonus token when every draft survives)
    k: int = Field(4, ge=1, le=16)


class ServingConfig(DeepSpeedConfigModel):
    """The ``"serving"`` block (scheduler knobs; README "Serving")."""

    #: decode slots (in-flight request capacity); bucketed to the next
    #: power of two so alternating deployments reuse compiled programs
    slots: int = Field(8, ge=1)
    #: KV block granularity for admission control (tokens per block)
    page_size: int = Field(16, ge=1)
    #: total KV token budget backing admission; None = slots x model
    #: context length (admission then only enforces per-request fit)
    kv_pool_tokens: Optional[int] = None
    #: total KV BYTE budget backing admission — converted to tokens from
    #: the cache's measured per-token footprint (codes + scales under
    #: ``kv_quant``), so quantized KV admits proportionally deeper on the
    #: same HBM; wins over ``kv_pool_tokens`` when both are set
    kv_pool_bytes: Optional[int] = None
    #: chunked prefill: prompt tokens consumed per prefill tick, so a 4k
    #: prompt cannot stall in-flight decodes for its whole prefill
    prefill_chunk: int = Field(16, ge=1)
    #: decode ticks guaranteed between two prefill-chunk ticks while
    #: decodes are in flight (0 = prefill greedily)
    prefill_interleave: int = Field(1, ge=0)
    #: queued requests beyond this are refused on submit
    max_queue: int = Field(1024, ge=1)
    #: served weight dtype (graft-quant-serve); resolution via
    #: :func:`resolve_weight_dtype`. ``int8``/``int4`` quantize the served
    #: param tree per group (weights only; embeddings/norms stay fp) and
    #: fuse dequant into the GEMM
    weight_dtype: Optional[str] = None
    #: target rows per quantization group along the contraction axis
    weight_group_size: int = Field(64, ge=1)
    #: content-hashed KV prefix caching (graft-prefix-cache); resolution
    #: via :func:`resolve_prefix_cache` (default ``on``). ``off`` is the
    #: A/B control arm: private blocks, no hash index, full prefill
    prefix_cache: Optional[str] = None
    #: int8 KV pools for the per-slot serving cache (the serving default:
    #: codes + per-(slot, position, head) scales, quantize-on-write /
    #: dequantize-on-read). False keeps fp KV for parity debugging
    kv_quant: bool = True
    #: emit a schema'd ``serve_tick`` telemetry event (queue depth,
    #: in-flight slots, TTFT p50/p99, BlockPool fragmentation — the
    #: fleet router/autoscaler input signals) every N ticks; 0 disables.
    #: Events are buffered (window-cadence flush), not fsynced per tick
    tick_telemetry_every: int = Field(1, ge=0)
    #: cadence (seconds) of the serving-role heartbeat block
    #: (``touch_heartbeat`` payload: slots in flight, queue depth, last
    #: tick monotonic) — a no-op unless running under a supervisor that
    #: set ``DS_ELASTIC_HEARTBEAT_FILE``
    heartbeat_interval: float = Field(1.0, ge=0.0)
    #: sampling (scheduler-global; speculation requires greedy)
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    speculation: SpeculationConfig = Field(default_factory=SpeculationConfig)

    @model_validator(mode="after")
    def _validate(self):
        _check(self.weight_dtype, WEIGHT_DTYPE_CHOICES, "weight_dtype")
        _check(self.prefix_cache, PREFIX_CACHE_CHOICES, "prefix_cache")
        if self.speculation.enabled and self.do_sample:
            raise ValueError("speculative decoding is only lossless under greedy "
                             "decoding; set do_sample=False or disable speculation")
        return self
