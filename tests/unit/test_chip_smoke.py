"""Rehearsal of ``chip_smoke.py`` without the chip: its phase functions at
the ``test`` preset on the CPU's virtual devices (Pallas kernels in
interpret mode), and its entry point's refusal to report success when the
platform is not a TPU."""

import json

import pytest

import jax

import chip_smoke


def test_train_phase_rehearsal():
    obs = chip_smoke.train_phase(preset="test", seq=128, devices=jax.devices()[:1])
    assert obs["losses"][-1] < obs["losses"][0]
    assert len(obs["scanned_losses"]) == 8


def test_serve_phase_rehearsal():
    obs = chip_smoke.serve_phase(preset="test", prompt=32, new=16, devices=jax.devices()[:1])
    assert obs["requests"] == 8 and obs["ticks"]["decode"] >= 16
    # fp32 on the CPU: the scheduler's arg-max is the reference forward's
    assert obs["worst_logit_gap"] <= 1e-3
    # and a decode tick on the quarter rung of 32 slots emits the whole program's tokens
    assert obs["decode_rung"] == {"ladder": [8, 32], "requests": 3, "parted_on_a_tie": 0,
                                  "worst_gap_at_a_parting": 0.0}


def test_zero3_phase_rehearsal():
    # at this size most leaves are below the planner's sharding threshold
    obs = chip_smoke.zero3_phase(preset="test", seq=128, devices=jax.devices()[:4],
                                 max_share=0.6)
    assert len(set(obs["sharded_bytes_per_device"])) == 1
    assert obs["max_loss_diff"] <= chip_smoke.ZERO3_LOSS_TOL


def test_zero3_phase_needs_four_devices():
    with pytest.raises(AssertionError, match="needs 4 devices"):
        chip_smoke.zero3_phase(preset="test", seq=128, devices=jax.devices()[:2])


def test_kernels_phase_rehearsal():
    obs = chip_smoke.kernels_phase(batch=2, seq=128, heads=4, head_dim=32, width=256)
    assert obs["compiled"] is False  # interpret mode off the chip
    assert obs["worst_bf16_roundings"]["moe_permute_fwd"] == 0.0
    assert [obs["worst_bf16_roundings"][f"kv_append_{n}"] for n in (1, 16, 5)] == [0.0] * 3
    assert obs["worst_bf16_roundings"]["dsa_select"] == 0.0
    assert {"flash_bwd", "flash_decode", "quant_matmul_int4", "sparse_bwd"} <= set(
        obs["worst_bf16_roundings"])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one_chip", "four_chips"])
def test_entry_point_refuses_without_a_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert out == "", "nothing may be printed on stdout without a TPU"
    assert not any(json.loads(line).get("ok") for line in out.splitlines() if line.startswith("{"))
