"""Deterministic fault injection by failure class.

Recovery behavior that is not exercised is folklore. This module injects
each documented failure class on demand so ``tools/fault_bench.py`` and
the tier-1 tests can assert the documented recovery, not assume it:

* **process death** — ``DS_FAULT_SPEC`` arms :func:`fault_point` hooks
  compiled into the engine (step boundaries) and the checkpoint publish
  path (right before the atomic rename), so a child under
  ``DSElasticAgent`` dies by SIGKILL at an exact, reproducible point;
* **storage corruption** — :func:`truncate_file` / :func:`bitflip_file` /
  :func:`corrupt_checkpoint` damage a published checkpoint the way a
  crashed writer or rotting disk would;
* **poisoned numerics** — :func:`overflow_injected_loss` +
  :func:`poison_batch` drive non-finite gradients through the real
  overflow-skip machinery (abort-after-K guard coverage);
* **flaky infrastructure** — :class:`FlakyCall` raises N
  backend-unavailable errors before succeeding (retry-policy coverage
  with the exact message text the installed runtime raises).

``DS_FAULT_SPEC`` grammar: comma-separated ``point=action[@arg]``, e.g.
``step=sigkill@3`` (SIGKILL at the step-3 boundary) or
``ckpt_pre_rename=sigkill`` (die between staging and publish — the torn
save). Unarmed, every hook is one cached dict lookup.
"""

import os
import signal
import time
from typing import Optional

FAULT_ENV = "DS_FAULT_SPEC"

_spec_cache = None
_spec_raw = None


def parse_fault_spec(raw: Optional[str] = None) -> dict:
    """``"step=sigkill@3,ckpt_pre_rename=sigkill"`` →
    ``{"step": ("sigkill", "3"), "ckpt_pre_rename": ("sigkill", None)}``."""
    spec = {}
    for item in (raw or "").split(","):
        item = item.strip()
        if not item:
            continue
        point, _, action = item.partition("=")
        action, _, arg = action.partition("@")
        if not point or not action:
            raise ValueError(f"bad {FAULT_ENV} entry {item!r}: want point=action[@arg]")
        spec[point.strip()] = (action.strip(), arg.strip() or None)
    return spec


def _active_spec() -> dict:
    global _spec_cache, _spec_raw
    raw = os.environ.get(FAULT_ENV, "")
    if raw != _spec_raw:  # re-read only when the env var changed (tests mutate it)
        _spec_raw, _spec_cache = raw, parse_fault_spec(raw)
    return _spec_cache


def _fire(action: str, point: str) -> None:
    if action == "sigkill":
        os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, no atexit — the real crash
    elif action == "exit1":
        os._exit(1)
    elif action == "hang":
        time.sleep(3600)
    else:
        raise ValueError(f"unknown fault action {action!r} at point {point!r}")


def fault_point(name: str, step: Optional[int] = None) -> None:
    """Injection hook. No-op unless ``DS_FAULT_SPEC`` arms ``name`` (and,
    for step-qualified points, the step matches the armed ``@arg``)."""
    spec = _active_spec()
    if name not in spec:
        return
    action, arg = spec[name]
    if arg is not None and step is not None and int(arg) != int(step):
        return
    _fire(action, name)


# ---------------------------------------------------------------------------
# storage corruption
# ---------------------------------------------------------------------------

def truncate_file(path: str, keep_fraction: float = 0.5) -> str:
    """Cut a file short — the signature of a writer killed mid-stream or a
    partially-replicated object."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(0, int(size * keep_fraction)))
    return path


def bitflip_file(path: str, offset: Optional[int] = None, seed: int = 0) -> str:
    """Flip one bit — silent storage corruption. Deterministic via seed."""
    import random
    size = os.path.getsize(path)
    assert size > 0, f"cannot bitflip empty file {path}"
    rng = random.Random(seed)
    offset = rng.randrange(size) if offset is None else offset
    with open(path, "r+b") as f:
        f.seek(offset)
        byte = f.read(1)
        f.seek(offset)
        f.write(bytes([byte[0] ^ (1 << rng.randrange(8))]))
    return path


def corrupt_checkpoint(base_dir: str, tag: str, mode: str = "truncate", seed: int = 0) -> str:
    """Damage a published checkpoint tag deterministically: picks the
    largest manifest-listed file (ties broken by name — the array data, not
    a json stub) and truncates or bit-flips it. Returns the damaged path."""
    from deepspeed_tpu.runtime.resilience.manifest import read_manifest

    tag_dir = os.path.join(base_dir, str(tag))
    manifest = read_manifest(tag_dir)
    if manifest and manifest.get("files"):
        rel = max(sorted(manifest["files"]), key=lambda r: manifest["files"][r]["bytes"])
        victim = os.path.join(tag_dir, rel)
    else:  # manifest-less checkpoint: largest file on disk
        candidates = [os.path.join(dp, f) for dp, _, fs in os.walk(tag_dir) for f in fs]
        assert candidates, f"no files under {tag_dir}"
        victim = max(sorted(candidates), key=os.path.getsize)
    if mode == "truncate":
        return truncate_file(victim)
    if mode == "bitflip":
        return bitflip_file(victim, seed=seed)
    raise ValueError(f"unknown corruption mode {mode!r}")


# ---------------------------------------------------------------------------
# poisoned numerics
# ---------------------------------------------------------------------------

FAULT_BOOST_KEY = "fault_boost"


def poison_batch(batch: dict, boost: float = float("inf")):
    """Add a per-sample ``fault_boost`` leaf (shape ``[B]`` so it rides the
    batch-sharding plumbing like any label). ``inf`` drives every gradient
    non-finite — the persistent-overflow class."""
    import numpy as np
    b = next(np.shape(l)[0] for l in batch.values() if np.ndim(l) > 0)
    out = dict(batch)
    out[FAULT_BOOST_KEY] = np.full((b,), boost, np.float32)
    return out


def overflow_injected_loss(base_loss_fn=None):
    """A ``loss_fn`` that multiplies the real loss by ``max(fault_boost)``
    when the batch carries one (see :func:`poison_batch`); otherwise it is
    exactly the base loss. The poison flows through the genuine
    grad/overflow/loss-scale machinery — nothing is mocked."""
    def loss(outputs, batch):
        import jax.numpy as jnp
        from deepspeed_tpu.runtime.engine import default_causal_lm_loss
        base = (base_loss_fn or default_causal_lm_loss)(outputs, batch)
        if isinstance(batch, dict) and FAULT_BOOST_KEY in batch:
            return base * jnp.max(batch[FAULT_BOOST_KEY])
        return base
    return loss


# ---------------------------------------------------------------------------
# flaky infrastructure
# ---------------------------------------------------------------------------

def make_backend_unavailable() -> RuntimeError:
    """An exception carrying the exact text jax 0.9.0 / libtpu 0.0.34
    raise while another process still holds the chip, so classifier
    coverage is against the real message, not a paraphrase."""
    return RuntimeError("Unable to initialize backend 'tpu': ABORTED: Internal error "
                        "when accessing libtpu multi-process lockfile.")


class FlakyCall:
    """Wrap ``fn`` to fail ``fails`` times (with ``exc_factory``'s error)
    before succeeding — the transient-failure injector for retry tests."""

    def __init__(self, fn, fails: int, exc_factory=make_backend_unavailable):
        self.fn = fn
        self.remaining = int(fails)
        self.exc_factory = exc_factory
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self.remaining > 0:
            self.remaining -= 1
            raise self.exc_factory()
        return self.fn(*args, **kwargs)
