"""GSPMD partition-quality regression: no "Involuntary full
rematerialization" on any dryrun mesh.

The warning (``spmd_partitioner.cc:652``) means GSPMD gave up on a
sharding transition and replicated a full tensor — on real hardware that
is a full-tensor ICI/DCN broadcast per step. Two
sources were fixed in round 4:

* the embedding GATHER on tensor/sequence meshes — fixed by
  ``models/common.lookup_table_view`` (reshard the table, not the gather
  output);
* the embedding-grad SCATTER-ADD on expert/fsdp meshes — fixed by
  defaulting ``embed_onehot_grad`` on (einsum backward partitions
  cleanly).

The compile runs in a subprocess because the warning is emitted by XLA's
C++ logging (not Python warnings) and the meshes need their own device
counts.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

WARNING = "Involuntary full rematerialization"


@pytest.mark.parametrize("mesh_fn", ["_dryrun_tp_sp_fsdp", "_dryrun_pipe", "_dryrun_moe"])
def test_dryrun_mesh_compiles_without_involuntary_remat(mesh_fn):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from envutil import cpu_subprocess_env

    env = cpu_subprocess_env(n_virtual_devices=8)
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import sys; sys.path.insert(0, {REPO!r}); "
         f"import jax; jax.config.update('jax_platforms', 'cpu'); "
         # NO persistent compile cache: the spmd_partitioner warning only
         # fires during an actual compile — a cache hit would pass vacuously
         f"import __graft_entry__ as g; g.{mesh_fn}(8)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"{mesh_fn} failed:\n{proc.stderr[-2000:]}"
    assert WARNING not in proc.stderr, (
        f"{mesh_fn} emitted GSPMD involuntary-remat warnings:\n"
        + "\n".join(l[:300] for l in proc.stderr.splitlines() if WARNING in l))
