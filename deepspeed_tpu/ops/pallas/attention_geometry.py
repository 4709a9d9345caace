"""Block-geometry + backward-recompute policy selection for the Pallas flash
attention engine.

The kernel in ``flash_attention.py`` is parameterized over its work
partitioning — forward and backward (q, kv) BLOCK sizes (what one grid step
stages in VMEM), the compute TILE a block is walked in, and whether the backward recomputes the
log-sum-exp or reads it from a stashed residual. Which combination is
fastest depends on the call shape (FlashAttention-2: the partitioning, not
the algorithm, is where the last 1.5-2x lives), so resolution is layered:

1. explicit per-call kwargs (``block_q=...`` etc.) and the model
   configuration's ``attention_blocks`` spec, which is where the engine's
   ``"attention"`` JSON config block lands (``runtime/engine.py``);
2. a shape-keyed winners file written by the kernel autotuner
   (``autotuning/attention_tuner.py``), where one exists at
   ``autotuning_results/attention_blocks.json`` or :func:`set_cache_path`
   points at one — none is committed: the winners measured on a v5e are the
   defaults below;
3. shape-keyed defaults, the v5e's measured winners (:func:`default_geometry`).

This module is import-light on purpose (no jax/pallas): the engine and the
bench tools consult it without paying for a Pallas import.

Spec grammar (config strings and cache entries share it):
``"block_q=1024,block_k=1024,block_q_bwd=1024,block_k_bwd=1024,tile=512,``
``policy=lse"`` — any subset of fields; a bare pair of ints
``"512,1024"`` means forward ``block_q,block_k``.
"""

import dataclasses
import json
import os
import threading
from typing import Any, Dict, Optional, Tuple

#: backward recompute policy: "lse" stashes the [B,H,L] log-sum-exp residual
#: in forward and reads it back; "recompute" stashes nothing extra and
#: re-runs the forward kernel inside the backward to regenerate it —
#: trades one extra forward's FLOPs for a smaller inter-pass residual
#: footprint (matters under remat at long L).
POLICY_CHOICES = ("lse", "recompute")

_FIELDS = ("block_q", "block_k", "block_q_bwd", "block_k_bwd", "tile", "policy")


@dataclasses.dataclass(frozen=True)
class AttentionGeometry:
    """One attention work partitioning. ``None`` fields mean "unset" and are
    filled by lower-precedence layers during :func:`resolve_geometry`."""

    block_q: Optional[int] = None
    block_k: Optional[int] = None
    block_q_bwd: Optional[int] = None
    block_k_bwd: Optional[int] = None
    tile: Optional[int] = None
    policy: Optional[str] = None

    def merged_over(self, base: "AttentionGeometry") -> "AttentionGeometry":
        """Fields set on ``self`` win; unset fields fall through to ``base``."""
        return AttentionGeometry(**{
            f: getattr(self, f) if getattr(self, f) is not None else getattr(base, f)
            for f in _FIELDS
        })

    def as_dict(self) -> Dict[str, Any]:
        return {f: getattr(self, f) for f in _FIELDS if getattr(self, f) is not None}

    def call_kwargs(self) -> Dict[str, Any]:
        """kwargs accepted by ``flash_attention`` (same names)."""
        return self.as_dict()

    def spec(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.as_dict().items())

    def validate(self) -> "AttentionGeometry":
        for f in ("block_q", "block_k", "block_q_bwd", "block_k_bwd", "tile"):
            v = getattr(self, f)
            if v is not None and (not isinstance(v, int) or v <= 0):
                raise ValueError(f"attention geometry: {f} must be a positive int, got {v!r}")
        if self.policy is not None and self.policy not in POLICY_CHOICES:
            raise ValueError(f"attention geometry: policy must be one of "
                             f"{POLICY_CHOICES}, got {self.policy!r}")
        return self


def from_dict(d: Dict[str, Any]) -> AttentionGeometry:
    unknown = set(d) - set(_FIELDS)
    if unknown:
        raise ValueError(f"attention geometry: unknown fields {sorted(unknown)}; "
                         f"known: {_FIELDS}")
    return AttentionGeometry(**d).validate()


def parse_spec(spec: str) -> AttentionGeometry:
    """Parse the shared spec grammar (see module docstring)."""
    spec = (spec or "").strip()
    if not spec:
        return AttentionGeometry()
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if all("=" not in p for p in parts):
        # bare "bq,bk" shorthand
        if len(parts) not in (1, 2):
            raise ValueError(f"attention geometry spec {spec!r}: bare form takes "
                             f"1-2 ints (block_q[,block_k])")
        ints = [int(p) for p in parts]
        return AttentionGeometry(block_q=ints[0],
                                 block_k=ints[1] if len(ints) > 1 else ints[0]).validate()
    d: Dict[str, Any] = {}
    for p in parts:
        if "=" not in p:
            raise ValueError(f"attention geometry spec {spec!r}: mixed bare/keyed fields")
        k, v = (s.strip() for s in p.split("=", 1))
        d[k] = v if k == "policy" else int(v)
    return from_dict(d)


# ---------------------------------------------------------------------------
# shape signatures + v5e defaults
# ---------------------------------------------------------------------------
def signature(lq: int, lk: int, head_dim: int, heads: int, batch: int,
              causal: bool, dtype: Any = None) -> str:
    """Shape key for the winners cache: the dims that change the kernel's
    work partitioning (seq, head_dim, heads, micro-batch, causal, dtype)."""
    dt = ""
    if dtype is not None:
        dt = "_" + getattr(dtype, "name", str(dtype))
    return (f"q{lq}_k{lk}_d{head_dim}_h{heads}_b{batch}_"
            f"{'causal' if causal else 'full'}{dt}")


def pick_block(length: int, preferred: int = 512) -> int:
    """Largest block from the standard chain that tiles ``length``."""
    for blk in sorted({preferred, 2048, 1024, 512, 256, 128, 64, 32, 16, 8}, reverse=True):
        if blk <= preferred and blk <= length and length % blk == 0:
            return blk
    return length


def default_geometry(lq: int, lk: int, head_dim: int, causal: bool) -> AttentionGeometry:
    """Shape-keyed defaults: the winners of the tuner's sweeps on a TPU v5e
    (bf16, causal, head 64; ``PERF.md`` section 6, PR 29).

    A block is the whole sequence where that fits — up to 2,048 rows in the
    forward at heads of 64, 1,024 otherwise and in the backward — and is
    walked in compute tiles of 512: at 1,024 positions (8 x 16 heads and
    4 x 25) one grid step a head with whole-sequence blocks beat every
    split of it in all three kernels, at 2,048 and 4,096 the forward won at
    2,048 x 2,048 and the backward at 1,024 x 1,024, and the tile of 512
    won at every block size. Longer sequences stream blocks of those sizes.
    """
    want = 2048 if head_dim <= 64 else 1024
    return AttentionGeometry(
        block_q=pick_block(lq, want),
        block_k=pick_block(lk, want),
        block_q_bwd=pick_block(lq, 1024),
        block_k_bwd=pick_block(lk, 1024),
        tile=512,
        policy="lse",
    )


# ---------------------------------------------------------------------------
# winners cache (written by autotuning/attention_tuner.py)
# ---------------------------------------------------------------------------
CACHE_BASENAME = "attention_blocks.json"
_DEFAULT_CACHE = os.path.join("autotuning_results", CACHE_BASENAME)

_lock = threading.Lock()
_cache_path_override: Optional[str] = None
_cache_memo: Optional[Tuple[str, float, Dict[str, Any]]] = None  # (path, mtime, data)


def cache_path() -> str:
    if _cache_path_override is not None:
        return _cache_path_override
    return _DEFAULT_CACHE


def set_cache_path(path: Optional[str]) -> None:
    """Point geometry lookup at a winners cache file (None = default)."""
    global _cache_path_override, _cache_memo
    with _lock:
        _cache_path_override = path
        _cache_memo = None


def load_cache(path: Optional[str] = None) -> Dict[str, Any]:
    """Winners cache: {signature: {"geometry": {...}, ...evidence}}. Memoized
    on (path, mtime) so per-call resolution costs no I/O in steady state."""
    global _cache_memo
    p = path or cache_path()
    try:
        mtime = os.path.getmtime(p)
    except OSError:
        return {}
    with _lock:
        if _cache_memo and _cache_memo[0] == p and _cache_memo[1] == mtime:
            return _cache_memo[2]
    try:
        with open(p) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict):
        return {}
    with _lock:
        _cache_memo = (p, mtime, data)
    return data


def store_winner(sig: str, geometry: AttentionGeometry, path: Optional[str] = None,
                 **evidence: Any) -> str:
    """Merge one shape's winner into the cache file (read-modify-write);
    returns the path written. Extra kwargs ride along as evidence
    (seconds, backend, candidate count, ...)."""
    global _cache_memo
    p = path or cache_path()
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    with _lock:
        data: Dict[str, Any] = {}
        try:
            with open(p) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                data = {}
        except (OSError, ValueError):
            pass
        data[sig] = {"geometry": geometry.as_dict(), **evidence}
        with open(p, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        _cache_memo = None
    return p


def lookup_cached(sig: str, path: Optional[str] = None) -> Optional[AttentionGeometry]:
    entry = load_cache(path).get(sig)
    if not entry or "geometry" not in entry:
        return None
    try:
        return from_dict(dict(entry["geometry"]))
    except (ValueError, TypeError):
        return None


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------
def resolve_geometry(lq: int, lk: int, head_dim: int, heads: int, batch: int,
                     causal: bool, dtype: Any = None,
                     overrides: Optional[AttentionGeometry] = None,
                     ) -> Tuple[AttentionGeometry, str]:
    """Resolve the full geometry for one call shape.

    Returns ``(geometry, source)`` where ``source`` names the
    highest-precedence layer that contributed any field — evidence for the
    perf ladder ("explicit" > "cache" > "default").
    Block sizes from every layer are clamped to divisors of the sequence
    lengths (a cache winner tuned at seq 8k must not break a seq 1000
    call); fields no layer sets come from the shape-keyed defaults.
    """
    layers = [("default", default_geometry(lq, lk, head_dim, causal))]
    cached = lookup_cached(signature(lq, lk, head_dim, heads, batch, causal, dtype))
    if cached is not None:
        layers.append(("cache", cached))
    if overrides is not None and overrides != AttentionGeometry():
        layers.append(("explicit", overrides.validate()))

    geom = AttentionGeometry()
    source = "default"
    for name, layer in layers:  # low → high precedence
        geom = layer.merged_over(geom)
        if layer != AttentionGeometry():
            source = name

    # the "default" layer populates every field, so geom is fully set here;
    # clamp every block to a divisor of its axis so a geometry tuned at one
    # shape can never make another shape untileable
    geom = AttentionGeometry(
        block_q=pick_block(lq, geom.block_q),
        block_k=pick_block(lk, geom.block_k),
        block_q_bwd=pick_block(lq, geom.block_q_bwd),
        block_k_bwd=pick_block(lk, geom.block_k_bwd),
        tile=geom.tile,
        policy=geom.policy,
    )
    return geom.validate(), source
