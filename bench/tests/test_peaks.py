"""The peaks table refuses unknown devices, and a run on a host without
an accelerator exits non-zero before it prints any result."""

import pytest

from bench import peaks, run


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_no_accelerator_exits_2_without_a_result(capsys):
    rc = run.main(["--workload", "b2-batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "accelerator" in out.err
