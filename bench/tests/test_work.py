"""Necessary bytes and operations per packet, against a hand count, and
the peaks table."""

import pytest

from bench import spec, work


def _config(name):
    return spec.load_json(spec.BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", ["flow-ddos-mlp", "flow-ddos-mlp-x4"])
def test_mlp_config_per_packet(name):
    c = _config(name)
    # packet row 4 words, register row 28 words read + written, key read
    # + written, verdict: 4 + 56 + 2 + 1 = 63 words
    assert work.bytes_per_packet(c) == 63 * 4 == 252
    # MLP 28-16-8-2: 2*(448 + 128 + 16) multiply-adds, 26 biases, 24
    # ReLUs; register update 2 counters + 2 EWMAs * 3 + 2 histograms;
    # readout 24 divisions
    mlp = 2 * (28 * 16 + 16 * 8 + 8 * 2) + (16 + 8 + 2) + (16 + 8)
    assert work.ops_per_packet(c) == mlp + 10 + 24 == 1268


def test_mat_mitigated_config_per_packet():
    c = _config("mitigate-mat")
    # + action row [hits, since] read + written, its key read + written
    assert work.bytes_per_packet(c) == (63 + 4 + 2) * 4 == 276
    # 28 features x (7 edge compares + 4 id-score adds), update, readout,
    # 4 for the action row
    assert work.ops_per_packet(c) == 28 * 11 + 10 + 24 + 4 == 346


@pytest.mark.parametrize("name, ops", [("flow-ddos-mlp", 1268 - 10 - 24),
                                       ("mitigate-mat", 346 - 10 - 24 - 4)])
def test_suffix_module_counts_its_ops(name, ops):
    # the suffix's share of the hand counts above: all but the register
    # update, the readout and the action row
    suffix = _config(name)["suffix"]
    assert spec.suffix_kind(suffix["kind"]).ops(suffix) == ops


def test_least_seconds_bound_and_peaks():
    c = _config("flow-ddos-mlp")
    t, bound = work.least_seconds(c, 1_000_000, "TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(252e6 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("source")
