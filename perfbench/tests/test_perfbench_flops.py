"""FLOP counts from shapes, and the peak table."""
import json

import pytest

from perfbench import flops
from perfbench.peaks import peak
from perfbench.tests.tiny import BENCH


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_smollm_forward_flops_per_token():
    cfg = _config("smollm-360m")
    # 32 layers x (2 x 9,830,400 projection and MLP weights
    # + 4 x 960 x 256.5 causal attention)
    want = 32 * (2 * 9_830_400 + 4 * 960 * 256.5)
    assert flops.llama_forward_flops_per_token(cfg, 512) == pytest.approx(want)
    assert 0.65e9 < want < 0.67e9


def test_proxy_flops_at_the_paper_widths():
    proxy = _config("scaledoc-paper-4096")["proxy"]
    row = 2 * (4096 * 512 + 512 * 512 + 512 * 128)
    assert flops.proxy_forward_flops_per_row(proxy, False) == row
    assert flops.proxy_train_flops_per_leaf(proxy) == pytest.approx(
        3 * 120 * 129 * (row + 2 * 128 * 64))
    assert flops.proxy_score_flops_per_leaf(proxy, 100_000) == pytest.approx(
        100_000 * (row + 256))
    assert flops.cold_leaf_flops(proxy, 100_000) == pytest.approx(
        flops.proxy_train_flops_per_leaf(proxy)
        + flops.proxy_score_flops_per_leaf(proxy, 100_000))


def test_peak_table_knows_v5e_and_refuses_others():
    row = peak("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(KeyError):
            peak(kind)
