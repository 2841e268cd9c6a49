"""The peaks table and the counts of operations and bytes, against hand
counts."""
import pytest

from bench import counts as K
from bench import layers

OLMO = dict(n_layers=16, d_model=2048, d_ff=8192, vocab_size=50304,
            n_heads=16, n_kv_heads=16, head_dim=128)
INTERN = dict(n_layers=24, d_model=2048, d_ff=8192, vocab_size=92544,
              n_heads=16, n_kv_heads=8, head_dim=128)


def test_peaks_table_is_keyed_by_device_kind():
    p = K.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        K.peaks("TPU v4")


def test_token_flops_hand_count():
    # olmo-1b: per layer q,k,v,o 4 * 2048^2 and the gated MLP 3 * 2048 * 8192
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert K.token_flops(OLMO, head=False) == 2 * 16 * per_layer
    assert K.token_flops(OLMO, head=True) == 2 * (16 * per_layer + 2048 * 50304)
    # internlm2-1.8b: 8 kv heads of 128 make k and v 2048 x 1024 each
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert K.token_flops(INTERN, head=True) == 2 * (24 * per_layer + 2048 * 92544)


def test_attention_and_prefill_flops():
    # one query over 10 keys: QK and PV, 2 flops per multiply-add, all heads
    assert K.attention_flops(OLMO, 10) == 16 * (2 * 10 * 2048 + 2 * 10 * 2048)
    # a chunk of 3 tokens at positions 5, 6, 7 sees 6 + 7 + 8 keys
    want = 3 * K.token_flops(OLMO, head=False) + K.attention_flops(OLMO, 21)
    assert K.prefill_chunk_flops(OLMO, 5, 3) == want


def test_paged_decode_call_hand_count():
    # 2 members, slots of 100 and 300 rows: internlm2 reads K and V rows of
    # 8 heads x 128 in bf16 per layer, plus 2 queries and 2 outputs of 16 x 128
    fl, by = K.paged_decode_call(INTERN, 2, [100, 300])
    assert fl == 2 * 24 * 4 * 400 * 16 * 128
    assert by == 2 * 24 * 2 * (2 * 400 * 8 * 128 + 2 * 2 * 16 * 128)
    peak = K.peaks("TPU v5 lite")
    assert K.min_seconds(fl, by, peak) == by / 819e9  # memory bound


class _Win:
    t0, t_close = 0.0, 2.0


class _Log:
    prefill = [(0, 0.1, 0, 4)]
    decode = [(0, 0.2, 0.3, [5, 7]), (1, 0.4, 0.5, [3])]


def test_window_flops_and_mfu_hand_count():
    m0 = dict(OLMO)
    m1 = dict(INTERN)
    rec = {"config": {"tiers": [{"model": m0, "k": 2}, {"model": m1, "k": 1}]},
           "decode_log": _Log(), "window": _Win(), "chips": 1,
           "device_kind": "TPU v5 lite"}
    want = (2 * (4 * K.token_flops(m0, head=False) + K.attention_flops(m0, 10))
            + 2 * (2 * K.token_flops(m0, head=True) + K.attention_flops(m0, 12))
            + (K.token_flops(m1, head=True) + K.attention_flops(m1, 3)))
    assert layers.window_flops(rec) == want
    from bench import spec
    mfu = spec.reader("mfu.tput")(rec)
    assert mfu == pytest.approx(100 * want / (2.0 * 197e12))
    # three active slots decoded over the 2 s window, one token each
    assert spec.reader("decoded_tok_s.tput")(rec) == pytest.approx(1.5)
    # the second the profiler held the loop is not window time
    rec["trace_host"] = {"stall_s": 1.0}
    assert spec.reader("decoded_tok_s.tput")(rec) == pytest.approx(3.0)
    assert spec.reader("mfu.tput")(rec) == pytest.approx(100 * want / (1.0 * 197e12))


def test_roofline_counts_only_calls_inside_the_traced_slice():
    rec = {"config": {"tiers": [{"model": dict(OLMO), "k": 2}]},
           "decode_log": type("L", (), {"decode": [(0, 1.0, 1.1, [64]), (0, 5.0, 5.1, [64])]})(),
           "trace": {"kernel_s": 1e-3}, "trace_host": {"t0": 0.5, "t1": 2.0},
           "device_kind": "TPU v5 lite"}
    fl, by = K.paged_decode_call(OLMO, 2, [64])
    want = 100 * K.min_seconds(fl, by, K.peaks("TPU v5 lite")) / 1e-3
    assert layers.paged_decode_roofline(rec) == pytest.approx(want)
