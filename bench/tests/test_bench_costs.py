"""FLOP and byte counts from shapes, against hand counts."""
import pytest

from bench import costs


def test_mamba2_flops_per_token_hand_count():
    cfg = {"n_layers": 2, "d_model": 4, "ssm_expand": 2, "ssm_headdim": 2, "ssm_state": 3,
           "ssm_ngroups": 1, "ssm_conv": 4, "vocab_size": 10}
    # d=4, di=8, nh=4, hd=2, ds=3, g=1: proj = 16 + 6 + 4 = 26, conv_dim = 14
    per_layer = (2 * 4 * 26          # in_proj
                 + 2 * 14 * 4        # conv
                 + 2 * 5 * 3 * 1     # C B^T within a chunk of 5
                 + 2 * 5 * 2 * 4     # that matrix times x
                 + 2 * 3 * 2 * 4     # chunk state
                 + 2 * 3 * 2 * 4     # state output
                 + 2 * 8 * 4)        # out_proj
    assert costs.mamba2_flops_per_token(cfg, chunk=5) == 2 * per_layer + 2 * 4 * 10


def test_mamba2_13b_flops_per_token():
    cfg = {"n_layers": 48, "d_model": 2048, "ssm_expand": 2, "ssm_headdim": 64,
           "ssm_state": 128, "ssm_ngroups": 1, "ssm_conv": 4, "vocab_size": 50280}
    f = costs.mamba2_flops_per_token(cfg, 256)
    assert 2.8e9 < f < 3.0e9  # ~2 x 1.4e9 parameters' worth, plus SSD terms


def test_minhash_kernel_bytes_and_buckets():
    assert costs.minhash_kernel_bytes(64, 128, 256) == 64 * 128 * 5 + 2 * 256 * 4 + 64 * 256 * 4
    # a call's own shape, not a padded one
    assert costs.minhash_kernel_bytes(65, 129, 200) == 65 * 129 * 5 + 2 * 200 * 4 + 65 * 200 * 4


def test_minhash_roofline_reads_the_stream_calls_in_the_window():
    from bench.metrics import minhash_hbm_roofline

    def kb(t0, docs):
        return {"name": "kernel:minhash", "kind": "kernel_batch", "t0": t0, "dur": 0.01,
                "attrs": {"docs": docs}}
    spans = [{"name": "plan:optimize", "kind": "span", "t0": 0.0, "dur": 5.0}, kb(1.0, 100),
             kb(10.0, 3), kb(20.0, 70), kb(21.0, 2)]
    # the stream's documents in order: 3 before the window, then 70 and 2
    shingles = [9] * 3 + [100] * 69 + [300] + [10, 20]
    ctx = {"trace": {"module_s": {"jit_minhash_pallas": 1e-6}}, "peaks": {"hbm_bytes_per_s": 1e12},
           "cfg": {"process": [{"name": "streaming_minhash_deduplicator", "num_permutations": 256}]},
           "checked": {"dedup_shingles": shingles}, "snaps": [{"wall": 15.0}], "spans": spans}
    want = costs.minhash_kernel_bytes(70, 300, 256) + costs.minhash_kernel_bytes(2, 20, 256)
    assert minhash_hbm_roofline.read(ctx) == pytest.approx(100.0 * want / 1e12 / 1e-6)
