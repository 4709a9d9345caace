"""graft-serve configuration: the ``"serving"`` config block.

Continuous in-flight batching (ISSUE 14 / ROADMAP item 1) is driven by a
small set of knobs. The scheduler that holds a ``ServingConfig`` reads it
and hands what shapes a program down as module configuration
(``serve_weight_dtype``) or constructor arguments: nothing outside the
configuration decides what a scheduler serves.
"""

from typing import Literal, Optional

from pydantic import Field, model_validator

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


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
    #: served weight dtype (graft-quant-serve): ``fp`` serves the param
    #: tree as stored; ``int8``/``int4`` quantize it per group (weights
    #: only; embeddings/norms stay fp) and fuse dequant into the GEMM
    #: (the choices of ``ops/quantizer/weights.py``)
    weight_dtype: Literal["fp", "int8", "int4"] = "fp"
    #: target rows per quantization group along the contraction axis
    weight_group_size: int = Field(64, ge=1)
    #: content-hashed KV prefix caching (graft-prefix-cache). ``on``
    #: ref-counts and content-addresses the BlockPool: committed full
    #: blocks index under a rolling hash, freed blocks with a live hash
    #: park on a cached-free LRU, and new prompts prefill only their
    #: uncached tail. ``off`` is the A/B control arm: private blocks, no
    #: hash index, full prefill
    prefix_cache: Literal["on", "off"] = "on"
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
        if self.speculation.enabled and self.do_sample:
            raise ValueError("speculative decoding is only lossless under greedy "
                             "decoding; set do_sample=False or disable speculation")
        return self
