"""Operation and byte counts against hand counts at LFW's shapes, and the
peaks table."""
import pytest

from bench import data, roofline


def test_gram_counts_at_lfw_shapes():
    sizes = data.split_sizes(13_233, 20)
    assert sorted(set(sizes)) == [661, 662] and sum(sizes) == 13_233
    flops, nbytes = roofline.gram_apply_counts(sizes, 2914, 7)
    # X^T Q and X S: 2 * 13,233 * 2,914 * 7 operations each
    assert flops == 2 * (2 * 13_233 * 2_914 * 7) == 1_079_706_936
    # X read once (13,233 x 2,914 f32); Q read and V written per node
    assert nbytes == 4 * 13_233 * 2_914 + 2 * 4 * 20 * 2_914 * 7 \
        == 157_507_528


def test_roofline_pct_is_memory_bound_at_lfw_shapes():
    flops, nbytes = roofline.gram_apply_counts(
        data.split_sizes(13_233, 20), 2914, 7)
    least = nbytes / 819e9                 # bytes bind: 192 us
    assert flops / 197e12 < least
    assert roofline.roofline_pct(flops, nbytes, 2 * least,
                                 "TPU v5 lite") == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1.0, 1.0, 1.0, "cpu")


def test_gram_roofline_reader():
    from bench import harness, spec, trace

    cfg = {"samples": 13_233, "n_nodes": 20, "d": 2914, "r": 7}
    flops, nbytes = roofline.gram_apply_counts(
        data.split_sizes(13_233, 20), 2914, 7)
    per_call_ns = 4 * nbytes / 819e9 * 1e9                    # 25%
    dv = trace.Device("/device:TPU:0", 0,
                      trace.collections.Counter(
                          {"batched_gram_apply_pallas.7": 100 * per_call_ns}),
                      trace.collections.Counter(
                          {"batched_gram_apply_pallas.7": 100}), None)
    view = harness.TraceView(trace.Reduced(1e9, 2, [dv]), cfg, {},
                             "TPU v5 lite")
    assert spec.metric_reader("gram_apply_roofline")(view) == \
        pytest.approx(25.0)
    assert spec.metric_reader("gram_apply.ms_per_solve")(view) == \
        pytest.approx(100 * per_call_ns / 1e6 / 2)
