"""The benchmark's own peak table and the functions that count a kernel's
operations and bytes."""

import pytest

from perfbench import kernel_costs as kc
from perfbench.peaks import PEAKS, UnknownDevice, peak_for


def test_v5e_peaks_are_the_published_ones():
    p = peak_for("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_per_s == 819e9
    assert p.ici_bytes_per_s == 200e9   # 1 600 Gbit/s; telemetry/introspect.py carries 4.0e10
    assert "Google Cloud" in p.source and all(v.source for v in PEAKS.values())


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100", ""])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(UnknownDevice):
        peak_for(kind)


def test_paged_decode_is_memory_bound_and_counts_each_row_once():
    flops, nbytes = kc.paged_decode(attended_tokens=2000, n_head=25, head_dim=64, itemsize=2, n_slots=8)
    assert flops == 4 * 2000 * 25 * 64
    assert nbytes == 2 * 2000 * 25 * 64 * 2 + 2 * 8 * 25 * 64 * 2
    t, bound = kc.min_seconds(flops, nbytes, peak_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_flash_counts_the_lower_triangle_and_five_products_backward():
    f_fwd, b_fwd = kc.flash_causal(4, 1024, 25, 64, 2, backward=False)
    f_bwd, b_bwd = kc.flash_causal(4, 1024, 25, 64, 2, backward=True)
    tri = 1024 * 1025 // 2
    assert f_fwd == 2 * 2 * 4 * 25 * tri * 64 and f_bwd * 2 == f_fwd * 5
    assert b_fwd == 4 * 4 * 1024 * 25 * 64 * 2 and b_bwd == 2 * b_fwd
    assert kc.min_seconds(f_fwd, b_fwd, peak_for("TPU v5 lite"))[1] == "compute"
