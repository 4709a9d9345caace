"""Fault-tolerant training subsystem.

Production TPU fleets live with preemption, host crashes, and flaky
networks as the steady state; this package is the layer that lets a fleet
lose a host and keep training:

* :mod:`manifest` — per-leaf checksum + shape/dtype manifests and file
  inventories that make a checkpoint *verifiable*; atomic-publish
  helpers (fsync + rename) that make it *crash-consistent*.
* :mod:`signals` — :class:`PreemptionGuard`: SIGTERM/SIGINT become a
  checkpoint request honored at the next step boundary instead of a
  lost run.
* :mod:`retry` — shared exponential-backoff-with-jitter policy with a
  per-attempt evidence log, wrapped around the flaky pieces of the
  tooling (backend start-up, chip probes).
* :mod:`faults` — deterministic fault injection by class (SIGKILL at a
  step boundary, torn saves, truncated/bit-flipped checkpoint files,
  persistent-overflow gradients, a transiently unavailable backend) so the
  documented recovery behavior is *tested*, not assumed
  (``tools/fault_bench.py`` runs the full matrix).
"""

from deepspeed_tpu.runtime.resilience.manifest import (MANIFEST_NAME, CheckpointCorruptError,
                                                       atomic_publish, build_manifest,
                                                       list_checkpoint_tags, read_manifest,
                                                       verify_checkpoint_dir, verify_state_leaves,
                                                       write_atomic_text)
from deepspeed_tpu.runtime.resilience.retry import RetryPolicy, classify_failure, is_transient
from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard

__all__ = ["MANIFEST_NAME", "CheckpointCorruptError", "atomic_publish", "build_manifest",
           "list_checkpoint_tags", "read_manifest", "verify_checkpoint_dir",
           "verify_state_leaves", "write_atomic_text", "RetryPolicy", "classify_failure",
           "is_transient", "PreemptionGuard"]
