"""Smoke-sized cells for the harness's CPU tests: the reduced
same-family configurations of both archs, a mix and each cell's limits,
written as data files into a temporary directory, which is how any new
cell is made."""

import json
import pathlib

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]

SMOKE_MODELS = {
    "granite-3-8b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                         head_dim=32, d_ff=256, vocab=512, padded_vocab=512),
    "minicpm3-4b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=256, vocab=512, padded_vocab=512,
                        mla=dict(q_lora_rank=64, kv_lora_rank=32,
                                 qk_nope_head_dim=16, qk_rope_head_dim=8,
                                 v_head_dim=16)),
}

SMOKE_MIX = {
    "why": "smoke", "burst_size": 6,
    "prompt": {"median": 40, "sigma": 0.9, "min": 8, "max": 100},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 20},
    "temperature": 0.7, "greedy_every": 2, "check_requests": 3,
    "serve": {"max_seq": 128},
}


# The smoke-size limits.  The CPU test process cannot turn off XLA's
# excess precision (the chip's runs do), so the program's roundings drift
# from the reference's and DAP's top-4 choices amplify the drift: on
# three seeds two CPU layers of width 128 read a pooled median rank of
# 0-0.5 and a worst request rank of 0.4-1.3, the 4-bit control 4-16 and
# 9.8-19.3.
SMOKE_LIMITS = {"median_rank": 2, "worst_request_rank": 4,
                "why": "smoke size on the CPU: the readings above"}


def smoke_config(name: str) -> dict:
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    conf.update(name=f"{name}-smoke", smoke=True)
    conf["model"].update(SMOKE_MODELS[name])
    conf["serve"].update(max_batch=4, paged_attn="fused")  # the chip's kernel
    return conf


def write_smoke_cell(cdir, tdir, kdir, name: str) -> str:
    """Write the files of the cell ``<name>-smoke.chat`` and return its name."""
    for d in (cdir, tdir, kdir):
        d.mkdir(exist_ok=True)
    cell = f"{name}-smoke.chat"
    (cdir / f"{name}-smoke.json").write_text(json.dumps(smoke_config(name)))
    (tdir / "chat.json").write_text(json.dumps(SMOKE_MIX))
    (kdir / f"{cell}.json").write_text(json.dumps(SMOKE_LIMITS))
    return cell


@pytest.fixture
def smoke_dirs(tmp_path):
    """``(config_dir, traffic_dir, check_dir)`` holding ``<arch>-smoke``
    configs, a ``chat`` mix and the limits of each ``<arch>-smoke.chat``
    cell; nothing in the repository is edited."""
    dirs = tmp_path / "configs", tmp_path / "traffic", tmp_path / "checks"
    for name in SMOKE_MODELS:
        write_smoke_cell(*dirs, name)
    return dirs
