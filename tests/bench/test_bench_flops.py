"""Operation counts and the peak table, against hand counts."""
import json

import pytest
from bench import flops
from bench_testing import ROOT


@pytest.mark.parametrize(
    "d_prime, hidden, rank, expected",
    [(10, 18, 10, 82_980),   # pems_sf-medium: MEDIUM widths, d' 10
     (9, 12, 6, 27_588)],    # nyc-small: SMALL widths, d' 9
)
def test_decode_and_fit_flops_match_hand_counts(d_prime, hidden, rank, expected):
    assert flops.decode_flops_per_entry(d_prime, hidden, rank) == expected
    assert flops.fit_flops_per_entry(d_prime, hidden, rank) == 3 * expected


@pytest.mark.parametrize("name", ["pems_sf-medium", "nyc-small"])
def test_config_files_state_the_counted_flops(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    counted = flops.decode_flops_per_entry(cfg["d_prime"], cfg["hidden"], cfg["rank"])
    assert counted == cfg["flops_per_decoded_entry"]
    assert len(cfg["folded_shape"]) == cfg["d_prime"]


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, peak) == (10.0, "flops")
    assert flops.roofline_seconds(10.0, 1000.0, peak) == (100.0, "bytes")


def test_bytes_per_entry_counts_indices_and_value():
    assert flops.decode_bytes_per_entry(10) == 44


def test_peaks_table_has_the_v5e_and_refuses_unknown_devices():
    from bench import harness

    peak = harness.peak_of("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "source" in peak
    with pytest.raises(KeyError):
        harness.peak_of("cpu")
