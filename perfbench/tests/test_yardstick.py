import pytest

from perfbench import spec, yardstick


def _cfg(name):
    return spec.load_config(spec.load_benchmark(), name)


def test_unknown_device_kind_raises():
    assert yardstick.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert yardstick.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError):
            yardstick.peaks(kind)


def test_train_step_flops_dense_by_hand():
    m = _cfg("mistral-7b-train")
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert spec.family_of(m).layer_params(m, True) == layer == 218_103_808
    tokens = 2 * 4096
    matmul = 6 * (4 * layer + 4096 * 32000) * tokens
    attn = 4 * 4 * 2 * 4096 * 4096 * 4096 / 2 * 3
    assert spec.family_of(m).train_step_flops(m, 2, 4096) == matmul + attn
    assert 5.2e13 < matmul + attn < 5.3e13        # the issue's 5.26e13


def test_moe_counts_active_experts_for_flops_all_for_bytes():
    m = _cfg("mixtral-8x7b")
    expert = 3 * 4096 * 14336
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    assert spec.family_of(m).layer_params(m, True) == attn + 2 * expert + 4096 * 8
    assert spec.family_of(m).layer_params(m, False) == attn + 8 * expert + 4096 * 8
    stored = spec.family_of(m).stored_param_bytes(m, 2.0)
    assert 12.0e9 < stored < 12.2e9               # the issue's 12.1 GB
    dense = _cfg("mistral-7b")
    assert 9.2e9 < spec.family_of(dense).stored_param_bytes(dense, 2.0) < 9.3e9


def test_decode_step_bytes_adds_live_kv():
    m = _cfg("mistral-7b")
    base = spec.family_of(m).decode_step_bytes(m, [], 2.0, 2.0)
    one = spec.family_of(m).decode_step_bytes(m, [1000], 2.0, 2.0)
    assert one - base == 2 * 20 * 1000 * 8 * 128 * 2
    assert base == spec.family_of(m).stored_param_bytes(m, 2.0) - 32000 * 4096 * 2


def test_percentile_and_spread():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert yardstick.percentile(xs, 50) == 3.0
    assert yardstick.percentile(xs, 95) == pytest.approx(4.8)
    assert yardstick.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        yardstick.percentile([], 95)
    assert yardstick.spread([10, 11, 12, 13, 14, 15]) > 0
