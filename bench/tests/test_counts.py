"""Operation and byte counts against hand-computed granite-3-8b numbers."""

import json

import pytest

from bench import cells, counts


@pytest.fixture(scope="module")
def granite():
    return json.loads((cells.CONFIG_DIR / "granite-3-8b.json").read_text())["model"]


def test_granite_per_token_counts(granite):
    # attention: q 4096x4096, k and v 4096x1024 each, o 4096x4096;
    # MLP 3 x 4096x12800; 40 layers: 40 x 199,229,440; head 4096 x 49155
    # = 201,338,880
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 12800
    assert counts.dense_macs_per_token(granite) == 40 * per_layer + 4096 * 49155
    assert counts.dense_macs_per_token(granite) == 8_170_516_480
    assert counts.kv_bytes_per_token(granite) == 2 * 1024 + 2 * 4  # 2056
    assert counts.qo_bytes_per_token(granite) == 4 * 32 * 128
    assert counts.attn_macs_per_layer(granite, 100) == 2 * 32 * 128 * 100


def test_granite_request_work(granite):
    # a 100-token prompt in chunks of 64 (keys read: 64, then 100), then
    # 3 fed decode tokens at positions 100..102 (keys 101, 102, 103)
    w = counts.request_work(granite, 100, 4, 0, 64)
    assert w["tokens"] == 103
    assert w["kv_bytes"] == 40 * (64 + 100 + 101 + 102 + 103) * 2056
    attn = sum(2 * 32 * 128 * (p + 1) for p in range(103))
    assert w["ops"] == 2 * (103 * 8_170_516_480 + 40 * attn)
    # a cached 64-token prefix is neither run nor re-read by chunks
    c = counts.request_work(granite, 100, 4, 64, 64)
    assert c["tokens"] == 39
    assert c["kv_bytes"] == 40 * (100 + 101 + 102 + 103) * 2056


def test_minicpm3_latent_bytes():
    m = json.loads((cells.CONFIG_DIR / "minicpm3-4b.json").read_text())["model"]
    assert counts.kv_bytes_per_token(m) == 256 + 32 + 4
    assert counts.attn_macs_per_layer(m, 0) == 40 * 64 * 256 + 40 * 256 * 64
