"""The work arithmetic against hand counts at the two served shapes, and the
peaks table."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def _dims(name):
    return work.Dims.of(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,params,kv_per_token,attn_per_ctx", [
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151936 (tied head);
    # K and V: 24 layers x 2 x 16 heads x 64 x 2 bytes
    ("qwen1.5-0.5b-hnn-spike", 463_863_808, 98_304, 98_304),
    # 40 x (4 x 2560^2 + 3 x 2560 x 6912) + 2560 x 151936;
    # K and V: 40 x 2 x 20 x 128 x 2 bytes
    ("qwen1.5-4b-hnn-spike-tp4", 3_560_898_560, 409_600, 409_600),
])
def test_hand_counts(name, params, kv_per_token, attn_per_ctx):
    d = _dims(name)
    assert work.matmul_params_per_token(d) == params
    assert d.kv_bytes_per_token == kv_per_token
    # 4 FLOPs per cached position per head dim per layer (QK^T and PV)
    assert work.attention_flops(d, 1) == attn_per_ctx
    assert work.decode_flops(d, 3, 1000) == 2 * params * 3 + 1000 * \
        attn_per_ctx


def test_paged_attention_bytes_05b():
    d = _dims("qwen1.5-0.5b-hnn-spike")
    # 100 pages x 16 positions x 98304 B, plus per layer and slot and
    # head: 64 bf16 queries (128 B), 64 int8 outputs, f32 scale and lse
    kv = 100 * 16 * 98_304
    io = 24 * 64 * 16 * (128 + 64 + 8)
    assert work.paged_attn_bytes(d, 100, 16, 64) == kv + io
    # on four chips every chip reads all queries and writes its partial
    assert work.paged_attn_bytes(d, 100, 16, 64, chips=4) == kv + 4 * io


def test_roofline_takes_the_larger_bound():
    peak = work.peak_for("TPU v5 lite")
    assert work.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert work.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)
    assert work.roofline_seconds(197e12, 2 * 819e9, peak) == \
        pytest.approx(2.0)


def test_peaks_lookup_and_unknown_kind(tmp_path):
    peak = work.peak_for("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["hbm_bytes"] == 16e9
    assert "TPU v5e" in peak["source"]
    with pytest.raises(KeyError, match="TPU v6 lite"):
        work.peak_for("TPU v6 lite")
    with pytest.raises(KeyError):
        work.peak_for("cpu")
