"""Cross-backend loss-curve parity harness tests (tools/parity_check,
BASELINE.md north star "bit-identical loss curves vs CPU reference").

Without a live TPU the enforceable half is: the harness itself is exactly
reproducible (two independent CPU processes produce bit-identical curves —
if THIS drifts, any TPU-vs-CPU comparison is meaningless), and the
compare() report detects drift at single-ULP resolution.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(REPO, "tools"))

import parity_check  # noqa: E402


def _run_curve(extra_env=None):
    from envutil import cpu_subprocess_env
    # ONE pinned device: XLA:CPU thread-per-device partitioning changes
    # reduction order, so the reference contract is 1-device (see
    # tools/parity_check.py docstring)
    env = cpu_subprocess_env(n_virtual_devices=1)
    env.update(extra_env or {})
    p = subprocess.run([sys.executable, os.path.join(REPO, "tools", "parity_check.py")],
                       env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert p.returncode == 0, p.stderr[-1500:]
    out = [l for l in p.stdout.strip().splitlines() if l.startswith("{")]
    return json.loads(out[-1])


def test_curve_is_bit_reproducible_across_processes():
    a = _run_curve()
    b = _run_curve()
    assert a["curve_hex"] == b["curve_hex"], (a["curve"], b["curve"])
    rep = parity_check.compare(parity_check.from_hex(a["curve_hex"]),
                               parity_check.from_hex(b["curve_hex"]))
    assert rep["bit_identical"] and rep["max_ulp"] == 0
    # the curve must actually train (loss decreasing overall), otherwise
    # bit-identity is vacuous
    vals = parity_check.from_hex(a["curve_hex"])
    assert vals[-1] < vals[0]


def test_compare_detects_single_ulp_drift():
    import struct
    base = [5.0, 4.5, 4.0]
    bumped = list(base)
    (i,) = struct.unpack(">I", struct.pack(">f", bumped[1]))
    bumped[1] = struct.unpack(">f", struct.pack(">I", i + 1))[0]
    rep = parity_check.compare(base, bumped)
    assert not rep["bit_identical"]
    assert rep["max_ulp"] == 1
    assert rep["max_abs_diff"] > 0


def test_hex_roundtrip_exact():
    import numpy as np
    vals = [3.14159, -0.0, 1e-30, 65504.0]
    round_tripped = parity_check.from_hex(parity_check.to_hex(vals))
    for v, rt in zip(vals, round_tripped):
        assert np.float32(rt) == np.float32(v) and np.signbit(np.float32(rt)) == np.signbit(np.float32(v))
