"""The comparison that decides ``correct``.

After the window, a sample of the greedy requests it finished is drawn
from the seed, always with the longest of them in it.  The plain
reference (``bench/reference/model.py``) runs once over each prompt
followed by the tokens served for it, and at every served position
reads the served token's rank among the reference's logits: how many
vocabulary entries it scores above that token (0 where the program
chose the reference's argmax).  Two numbers are compared:

* ``median_rank``, the median over every served position: steady from
  seed to seed, and what the lower-precision control fails;
* ``worst_request_rank``: for each request, the geometric mean of
  ``1 + rank`` over its positions, less one, and the largest of these,
  so one request served wrong among sound ones fails it.  Requests that
  served fewer than ``MIN_TOKENS`` tokens count only in the pooled
  median: a mean of two or three ranks swings on sound runs as far as
  the control reads.

Their limits are the cell's own file, ``checks/<cell>.json``
(``{"median_rank": n, "worst_request_rank": n, "why": "<the readings
that set them>"}``), which ``bench/cells.py`` reads into ``Cell.limits``;
a cell without that file, or with a limit missing or ``null``, is not
correct.
Each request's median rank, ``mismatch_share`` and the widest and the
mean logit gap are printed beside them and compared with nothing.

The control (``--control w4``) is the same reference with 4-bit weights
in the program's place.  At the same positions it serves the token it
ranks first, and that stream is judged by the same numbers and limits:
its verdict is the run's ``correct``, and the program's readings are
printed beside it.
"""

from __future__ import annotations

import numpy as np

CONTROLS = {"w4": (4, 8)}  # (weight bits, activation bits)
MIN_TOKENS = 5  # served tokens a request needs to count in worst_request_rank
NUMBERS = ("median_rank", "worst_request_rank")  # what a limit is set on


def limits_for(cell) -> dict:
    """``{number: limit}`` of ``checks/<cell>.json`` for each compared
    number; a limit the file does not set is ``None``, and fails."""
    return {name: cell.limits.get(name) for name in NUMBERS}


def readings(logits, tokens, lengths) -> dict:
    """What a token stream reads against the reference logits, ``lengths``
    being the served tokens of each request in order."""
    chosen = logits[np.arange(len(tokens)), tokens]
    gap = logits.max(axis=-1) - chosen
    rank = np.sum(logits > chosen[:, None], axis=-1)  # 0: the reference's best
    by_request = np.split(rank, np.cumsum(lengths)[:-1])
    long_enough = [r for r in by_request if len(r) >= MIN_TOKENS] or by_request
    return {
        "worst_request_rank": max(
            float(np.expm1(np.mean(np.log1p(r)))) for r in long_enough),
        "median_rank": float(np.median(rank)),
        "mismatch_share": float(np.mean(rank > 0)),
        "logit_gap": float(gap.max()),
        "mean_logit_gap": float(gap.mean()),
        "request_median_ranks": [float(np.median(r)) for r in by_request],
        "request_ranks": [r.tolist() for r in by_request],
    }


def sample(cell, seed, finished):
    """``[(request, served tokens)]``: the longest greedy request the
    window finished, then others drawn from the seed, up to the mix's
    ``check_requests``."""
    done = [(req, res) for req, res in finished if req.greedy and res.ok]
    if not done:
        return []
    lengths = [len(req.prompt) + res.n_generated for req, res in done]
    first = int(np.argmax(lengths))
    rest = [i for i in range(len(done)) if i != first]
    rng = np.random.default_rng([seed % 2**32, 17])
    rng.shuffle(rest)
    n = cell.mix.get("check_requests", 4)
    pick = [first] + rest[: n - 1]
    out = []
    for i in pick:
        req, res = done[i]
        served = np.asarray(res.tokens[len(req.prompt):], np.int32)
        out.append((req, served))
    return out


def padded_length(cell) -> int:
    """The reference's fixed sequence length: the mix's longest request
    (document, prompt and answer at their largest), at most the cell's
    ``max_seq``, rounded up; one compiled shape for every seed."""
    from bench.reference.model import SEQ_MULTIPLE
    from bench.traffic import longest_request

    serve = {**cell.config["serve"], **cell.mix.get("serve", {})}
    n = min(longest_request(cell.mix), serve["max_seq"])
    return -(-n // SEQ_MULTIPLE) * SEQ_MULTIPLE


def gaps(cell, seed, picked, prec=None):
    """``(logits, served)``: the reference's float32 logits at every
    served position of ``picked`` (``prec`` narrows its wire for the
    control), and the tokens served there, flat in the same order."""
    from bench.reference import model as ref

    arch = ref.Arch.from_json(cell.config["model"])
    t = padded_length(cell)
    seqs, rows, served = [], [], []
    for i, (req, out) in enumerate(picked):
        seqs.append(np.concatenate([req.prompt, out[:-1]]))
        p = len(req.prompt)
        rows.extend(i * t + p - 1 + j for j in range(len(out)))
        served.append(out)
    n = cell.mix.get("check_requests", 4)
    while len(seqs) < n:  # fixed batch: one compiled shape for every seed
        seqs.append(np.zeros(1, np.int32))
    tokens = ref.pad_sequences(seqs, t)
    # rows padded to a fixed count too, so the head compiles once
    n_rows = n * cell.mix["output"]["max"]
    padded = np.zeros(n_rows, np.int64)
    padded[: len(rows)] = rows
    kw = {} if prec is None else {"prec": prec}
    logits = np.asarray(ref.forward_logits(arch, seed, tokens, padded, **kw))
    return logits[: len(rows)], np.concatenate(served)


def failed(cell) -> dict:
    """The verdict of a check that read nothing."""
    return {"correct": False, "readings": None, "control": None,
            "check": {k: {"value": None, "limit": v}
                      for k, v in limits_for(cell).items()}}


def verdict(read: dict, limits: dict) -> dict:
    return {
        "correct": all(
            v is not None and read[k] <= v for k, v in limits.items()),
        "check": {k: {"value": read[k], "limit": v} for k, v in limits.items()},
    }


def run(cell, seed, finished, control=None) -> dict:
    """The program's verdict, or with ``control`` the control's, and the
    readings of both."""
    from bench.reference import model as ref

    limits = limits_for(cell)
    picked = sample(cell, seed, finished)
    if not picked:
        return failed(cell)
    logits, served = gaps(cell, seed, picked)
    lengths = [len(out) for _, out in picked]
    read = readings(logits, served, lengths)
    read["tokens"] = int(len(served))
    out = {"readings": read, "control": None, **verdict(read, limits)}
    if control:
        w_bits, a_bits = CONTROLS[control]
        c_logits, _ = gaps(cell, seed, picked, ref.Precision(w_bits, a_bits))
        c_read = readings(logits, c_logits.argmax(axis=-1), lengths)
        out.update(verdict(c_read, limits), control={"name": control, **c_read})
    return out
