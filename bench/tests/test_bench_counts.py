"""The yardstick's work counts against hand counts at a small size, and the
peak table's refusal of an unknown device."""
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)

from bench.counts import granite, pca
from bench.harness import load_peaks


def test_granite_flops_per_token_by_hand():
    cfg = dict(d_model=8, num_heads=2, num_kv_heads=1, d_ff=16, vocab_size=10,
               num_layers=3)
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; ffn 3 * 8 * 16 = 384
    assert granite.matmul_params(cfg) == 3 * (192 + 384) + 10 * 8
    # causal attention, seq 4: keys per query average 2.5; fwd 2*2*8*2.5 = 80
    # per layer, fwd+bwd 240, three layers 720
    assert granite.attention_flops_per_token(cfg, 4) == pytest.approx(720.0)
    assert granite.train_flops_per_token(cfg, 4) == pytest.approx(
        6 * (3 * 576 + 80) + 720)


def test_granite_8b_two_layers_matches_the_published_count():
    cfg = dict(d_model=4096, num_heads=32, num_kv_heads=8, d_ff=14336,
               vocab_size=49152, num_layers=2)
    assert granite.matmul_params(cfg) == 637_534_208
    assert granite.train_flops_per_token(cfg, 4096) == pytest.approx(4.0266e9, rel=1e-4)


def test_pca_round_bytes_and_flops_by_hand():
    # z: 8 x 4 floats; 2 gossip rounds read + write a 2 x 4 buffer
    assert pca.round_bytes(batch=8, dim=4, nodes=2, rounds=2) == 4 * (32 + 2 * 2 * 8)
    assert pca.round_flops(batch=8, dim=4) == 2 * 2 * 32
    peaks = {"hbm_bytes_per_s": 1e3, "bf16_flops_per_s": 1e12}
    assert pca.least_seconds(batch=8, dim=4, nodes=2, rounds=2, peaks=peaks) == \
        pytest.approx(0.256)


def test_peaks_table_has_v5e_and_refuses_an_unknown_kind():
    v5e = load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(SystemExit, match="no peaks"):
        load_peaks("TPU v99")
