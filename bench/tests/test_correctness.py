"""``correct`` from a whole harness run at smoke size on the CPU: the
sound program passes; the lower-precision control, a program whose
tokens are altered where they are made, and one that alters only one
batch row's tokens do not.  The look for a chip is
skipped; everything else is the run the chip makes.  (The CPU serves
the MLA path only in float32, so these runs are granite's.)"""

import contextlib
import io
import json

import jax.numpy as jnp
import pytest

from bench import check, run

CELL = "granite-3-8b-smoke.chat"


def _run(smoke_dirs, *extra):
    cdir, tdir, kdir = smoke_dirs
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--seed", str(2**31 + 5),
                       "--seconds", "0.5", "--trace", "0", *extra],
                      require_tpu=False, config_dir=cdir, traffic_dir=tdir,
                      check_dir=kdir)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_passes_and_the_control_fails(smoke_dirs):
    out = _run(smoke_dirs)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 6
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p90_s", "tpot_p90_ms", "setup_s"}
    for name, item in out["check"].items():
        assert item["value"] <= item["limit"]
    control = _run(smoke_dirs, "--control", "w4")
    assert control["correct"] is False  # the control's own verdict
    assert control["control"]["name"] == "w4" and control["readings"]["tokens"] > 0
    assert any(item["value"] > item["limit"] for item in control["check"].values())


@pytest.mark.parametrize("limits", [
    None, {"median_rank": None, "worst_request_rank": 4}, {"median_rank": 2}])
def test_cell_without_limits_is_not_correct(smoke_dirs, limits):
    """No limits file, or a limit no reading has set or the file leaves
    out: never correct."""
    path = smoke_dirs[2] / f"{CELL}.json"
    if limits is None:
        path.unlink()
    else:
        path.write_text(json.dumps(limits))
    out = _run(smoke_dirs)
    assert out["correct"] is False
    assert out["failed"] == 0 and out["readings"]["tokens"] > 0
    assert list(out)[-1] == "check" and set(out["check"]) == set(check.NUMBERS)


def _alter(monkeypatch, rows):
    """Replace the greedy token of the batch rows ``rows(batch)`` picks by
    the next id where it is sampled, in the mixed step's sampler and
    inside the fused decode loop."""
    from repro.core import sampling
    from repro.serve import engine

    real = sampling.sample_tokens

    def altered(logits, temps, top_ks, top_ps, seeds, positions):
        tok = real(logits, temps, top_ks, top_ps, seeds, positions)
        bad = rows(tok.shape[0]) & (temps == 0)
        return jnp.where(bad, (tok + 1) % logits.shape[-1], tok)

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    monkeypatch.setattr(engine, "sample_tokens", altered)


def test_altered_tokens_fail(smoke_dirs, monkeypatch):
    _alter(monkeypatch, lambda b: jnp.ones(b, bool))
    out = _run(smoke_dirs)
    assert out["correct"] is False
    assert any(item["value"] > item["limit"] for item in out["check"].values())


def test_one_altered_row_fails(smoke_dirs, monkeypatch):
    """Only batch slot 0 serves wrong tokens; the other slots' requests
    are sound, and the pooled median rank stays where it was."""
    _alter(monkeypatch, lambda b: jnp.arange(b) == 0)
    out = _run(smoke_dirs)
    assert out["correct"] is False
    worst = out["check"]["worst_request_rank"]
    assert worst["value"] > worst["limit"]
    ranks = out["readings"]["request_median_ranks"]
    assert sum(r > worst["limit"] for r in ranks) < len(ranks)
